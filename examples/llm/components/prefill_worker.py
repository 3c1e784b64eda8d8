"""PrefillWorker — disagg prefill side of the example graphs.

Pulls remote-prefill work from the coordinator queue, computes KV, pushes
blocks to the decode worker's transfer endpoint (device-to-device when
colocated, TCP over DCN otherwise) and notifies.  Reference analogue:
examples/llm/components/prefill_worker.py.
"""

from __future__ import annotations

import asyncio
import logging

from dynamo_tpu.sdk import async_on_start, dynamo_endpoint, service

from .worker import NAMESPACE, build_engine

log = logging.getLogger("examples.prefill_worker")


@service(dynamo={"namespace": NAMESPACE}, resources={"tpu": 1})
class PrefillWorker:
    def __init__(self):
        self._cfg = dict(self.service_config)
        self._task = None
        self.worker = None

    @async_on_start
    async def boot(self):
        from dynamo_tpu.llm.workers import PrefillWorker as EnginePrefillWorker

        from .worker import resolve_cfg_model

        rt = self.dynamo_runtime
        # off-loop: the model build blocks for seconds (see worker.boot)
        engine, _card = await asyncio.to_thread(
            build_engine, await resolve_cfg_model(self._cfg, rt))
        self.worker = EnginePrefillWorker(engine, rt.coordinator, NAMESPACE)
        self._task = asyncio.ensure_future(self.worker.run())

    async def shutdown(self):
        if self.worker is not None:
            self.worker.request_stop()
        if self._task is not None:
            try:
                await asyncio.wait_for(self._task, timeout=2)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                self._task.cancel()
        # join the engine thread, as the colocated worker does: left running
        # it goes on stepping into the process-wide step timeline
        engine = getattr(self.worker, "engine", None)
        if hasattr(engine, "shutdown"):
            engine.shutdown()

    @dynamo_endpoint
    async def status(self, req: dict):
        yield {"handled": self.worker.handled if self.worker else 0}
