"""AsyncLLMEngine — asyncio front door over the engine thread.

Implements the runtime's AsyncEngine contract (generate(Context[BackendInput])
→ stream of LLMEngineOutput) so the engine slots directly into pipelines,
the HTTP service, and distributed endpoints.  The engine core runs on its
own thread (JAX dispatch blocks); outputs cross back into per-request
asyncio queues in ONE loop.call_soon_threadsafe per event loop per
dispatch: a request's ``emit`` only collects on the engine thread, and the
core says where a batch is complete (``EngineCore.flush_outputs``: the end
of a dispatch's host work, of a step, of ``fail_all``).  A wake-up a row
is a system call and a hand-over of the interpreter lock a row, while the
device's next program stands finished (docs/engine_scheduling.md).

Cancellation: a stopped/killed Context aborts the request in the core at
the next step boundary (reference: AsyncEngineContext::stop_generating
carried as ControlMessage::{Stop,Kill}, lib/runtime/src/engine.rs:76-84).
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import gc
import logging
import threading
from typing import AsyncIterator

import jax

from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import BackendInput, LLMEngineOutput
from dynamo_tpu.obs import tracing
from dynamo_tpu.runtime.engine import AsyncEngine, Context

log = logging.getLogger("dynamo_tpu.engine")

__all__ = ["AsyncLLMEngine"]


# put into a stream's queue when its Context is stopped or killed
_STOPPED = object()


def _deliver(batch: list) -> None:
    """On the event loop: a flush's outputs into their streams' queues, in
    the order the engine emitted them."""
    for put, out in batch:
        put(out)


def settle_heap() -> None:
    """Take what building a program left on the heap out of the collector's
    sight.  The jaxprs, lowered modules and wrappers of a compiled program
    live as long as the program, and every full collection of CPython's
    collector walked all of them again: 0.25 s with 18 programs built, two
    or three times a minute under load, every row waiting (ROADMAP S11).
    Collect the garbage the trace made, then freeze the rest; what dies by
    reference count is still freed."""
    gc.collect()
    gc.freeze()


class AsyncLLMEngine(AsyncEngine):
    def __init__(self, core: EngineCore):
        self.core = core
        self._wake = threading.Event()
        self._shutdown = False
        self._thread: threading.Thread | None = None
        # resolves (with the exception) when a step failed while building
        # its program; the engine thread has then stopped for good.  The
        # serving entrypoint awaits it and exits non-zero (cli.py run).
        self.failed: concurrent.futures.Future = concurrent.futures.Future()
        # emitted on the engine thread, not yet handed to the streams:
        # event loop -> [(the stream's put_nowait, output)]
        self._outbox: dict = collections.defaultdict(list)
        core.flush_outputs = self._flush_outbox

    # --------------------------------------------------------------- lifecycle
    def start(self) -> "AsyncLLMEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="engine-core", daemon=True
            )
            self._thread.start()
        return self

    def shutdown(self) -> None:
        self._shutdown = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if hasattr(self.core, "close"):
            self.core.close()  # stop the kv-offload thread, if any

    def _run(self) -> None:
        # jax reports every trace/lower/compile stage it runs; one on this
        # thread during a step means the step was building a program
        # (first call of that shape) rather than running a built one
        me = threading.get_ident()
        builds = 0

        def on_compile_stage(event: str, duration: float, **kw) -> None:
            nonlocal builds
            if "/compile/" in event and threading.get_ident() == me:
                builds += 1

        jax.monitoring.register_event_duration_secs_listener(on_compile_stage)
        try:
            while not self._shutdown:
                before = builds
                try:
                    did_work = self.core.step()
                except Exception as e:
                    log.exception(
                        "engine step failed; failing in-flight requests")
                    self.core.fail_all()
                    if builds != before:
                        # a compile error (Mosaic refusing a kernel, a
                        # missing lowering, XLA out of memory) is not a
                        # per-request failure: every request of that shape
                        # fails the same way, so the server must not stay up
                        log.critical(
                            "the failed step was building its program; "
                            "stopping the engine")
                        self.failed.set_result(e)
                        return
                    did_work = False
                if builds != before:
                    settle_heap()
                if not did_work:
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
        finally:
            jax.monitoring.unregister_event_duration_listener(
                on_compile_stage)
            gc.unfreeze()  # a stopped engine's programs may be collected

    def _flush_outbox(self) -> None:
        """Hand what the engine thread has emitted to the streams: one
        wake-up per event loop, whatever the number of outputs.  Runs on
        the thread that emitted (``EngineCore.flush_outputs``)."""
        outbox = self._outbox
        if not outbox:
            return
        counts = self.core.counts
        for loop, batch in outbox.items():
            counts.emit_hops_total += 1
            counts.outputs_emitted_total += len(batch)
            try:
                loop.call_soon_threadsafe(_deliver, batch)
            except RuntimeError:
                # a closed loop costs its own streams, no other loop's
                log.warning("event loop closed: %d outputs dropped",
                            len(batch))
        outbox.clear()

    async def run_on_engine(self, fn):
        """Run ``fn`` on the engine thread at a step boundary (cache/block
        bookkeeping must stay single-writer); await its result."""
        fut = self.core.run_on_step(fn)
        self._wake.set()
        return await asyncio.wrap_future(fut)

    # ---------------------------------------------------------------- generate
    def generate(self, request: Context[BackendInput]) -> AsyncIterator[LLMEngineOutput]:
        return self._generate(request)

    def generate_ex(
        self,
        request: Context[BackendInput],
        *,
        remote_prefill: bool = False,
        remote_decode: bool = False,
        on_allocated=None,
    ) -> AsyncIterator[LLMEngineOutput]:
        """generate() with disaggregation knobs (ref RemotePrefillParams,
        vllm patch remote_prefill.py): ``remote_prefill`` stalls the request
        until a prefill worker delivers KV; ``remote_decode`` runs prefill
        only and holds the blocks for transfer-out."""
        return self._generate(
            request,
            remote_prefill=remote_prefill,
            remote_decode=remote_decode,
            on_allocated=on_allocated,
        )

    async def _generate(
        self,
        request: Context[BackendInput],
        *,
        remote_prefill: bool = False,
        remote_decode: bool = False,
        on_allocated=None,
    ) -> AsyncIterator[LLMEngineOutput]:
        if self.failed.done():
            # the engine thread is gone: a queued request would never step
            raise RuntimeError(
                f"engine stopped after a failed build: {self.failed.result()!r}")
        inp = request.data
        loop = asyncio.get_running_loop()
        out_q: asyncio.Queue = asyncio.Queue()  # LLMEngineOutput | _STOPPED

        outbox, put = self._outbox, out_q.put_nowait

        def emit(out: LLMEngineOutput) -> None:
            # engine thread; leaves for the loop at the core's next flush
            outbox[loop].append((put, out))

        # dtspan: one span per engine-side generation, parented on the
        # caller's context (HTTP root span or a TCP server hop) so the
        # frontend's trace id continues through the engine.  The engine
        # thread has no ambient contextvar — req.trace carries the pair.
        span = tracing.start_span(
            "engine.generate", attrs={"request_id": request.id})
        req = EngineRequest(
            request_id=request.id,
            prompt=list(inp.token_ids),
            sampling=inp.sampling,
            stops=inp.stops,
            emit=emit,
            remote_prefill=remote_prefill,
            remote_decode=remote_decode,
            on_allocated=on_allocated,
            trace=span.context(),
        )
        if tracing.enabled():
            tracing.collector.bind_request(request.id, span.trace_id)
        self.core.submit(req)
        self._wake.set()
        # for the front end's pre-submit histogram, as queue_wait_s below
        request.annotations["submitted_at"] = req.submitted_at

        # the one task a stream costs: a stopped or killed Context wakes the
        # stream through its own queue, so a token is a plain queue get
        cancel_task = asyncio.ensure_future(request.stopped())

        def on_stop(task: asyncio.Future) -> None:
            if not task.cancelled():   # cancelled: the stream's own finally
                put(_STOPPED)

        cancel_task.add_done_callback(on_stop)
        try:
            while True:
                out = await out_q.get()
                if out is _STOPPED:
                    self.core.abort(req.request_id)
                    self._wake.set()
                    # drain on until the core confirms cancellation
                    continue
                if (req.queue_wait_s is not None
                        and "queue_wait_s" not in request.annotations):
                    # surface admission wait for the HTTP histogram
                    request.annotations["queue_wait_s"] = req.queue_wait_s
                yield out
                if out.finished:
                    return
        finally:
            # a consumer abandoning the stream lands here from the get
            cancel_task.cancel()
            if not request.is_stopped and req.finish_reason is None:
                # consumer dropped the stream mid-generation
                self.core.abort(req.request_id)
                self._wake.set()
            span.set(
                finish=str(req.finish_reason) if req.finish_reason else "",
                queue_wait_s=req.queue_wait_s or 0.0,
            ).end()
