"""Negative controls of the check that decides ``correct`` in the looped
decoder's cell (``ouro-2.6b.reason-closed``): the program is broken in one
stated way, the float32 reference is left as it is, and the cell's own rule
(``check`` of cellbench/configs/ouro-2.6b.json) must reject every seed.

    chiprun -- python3 scripts/ouro_controls.py --control ut3 --seeds 11,12,13

``none``           the program as it is (the margins; must pass)
``ut3``            the program runs 3 passes, the reference the published 4
``shared-cache``   every pass writes and reads the last pass's cache layers:
                   one K/V shared by the passes (a quarter of the cache)
``int8-weights``   the program's matrices rounded to int8 (weight-only
                   quantisation, models/quant.py); the reference reads the
                   bf16 weights they were rounded from

It is ``python3 -m cellbench.sweep --check-seeds`` with the program changed
under it: the same prompts, the same two batch compositions, the same
teacher-forced comparison (cellbench/check.py), one set-up for all seeds.
``--num-blocks`` shrinks the cache (the check needs 38 blocks; int8 and bf16
weights are both resident in the last control).  ``--root`` / ``--workload``
/ ``--rehearse`` point it at a tiny copy on the CPU.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from cellbench import check, run as runner, server, spec  # noqa: E402

CONTROLS = ("none", "ut3", "shared-cache", "int8-weights")


def share_one_cache(passes: int, layers: int) -> None:
    """Every pass's layer l -> cache layer (passes - 1) * layers + l."""
    import dynamo_tpu.models.llama as llama

    shared = lambda li: (passes - 1) * layers + li % layers
    for name, at in (("write_kv_cache_layer", 1), ("paged_attention_layer", 2),
                     ("prefill_attention", 4), ("ragged_prefill_attention", 4)):
        real = getattr(llama, name)

        def wrapped(*a, _real=real, _at=at, **kw):
            a = list(a)
            a[_at] = shared(a[_at])
            return _real(*a, **kw)

        setattr(llama, name, wrapped)


async def one_seed(served, ref_params, config, settings, seed, root, gen, forward):
    n = int(settings["check"]["max_tokens"])
    prompts = check.check_prompts(settings, seed, served.vocab_size, gen)
    answers = await check.collect(served.url, served.name, prompts, n)
    errors = [a["error"] for a in answers if a["error"]]
    if errors:
        return {"ok": False, "why": f"check requests failed: {errors[:3]}"}
    loop = asyncio.get_running_loop()
    all_deltas: list[float] = []
    for a in answers:
        lps = await loop.run_in_executor(
            None, check.reference_logprobs, forward, ref_params, a, n)
        all_deltas += check.deltas(a, lps)
    return check.verdict(all_deltas, config["check"])


async def main_async(a, root: Path, cell, settings: dict, workdir: str) -> int:
    import jax

    reference_cfg = cell.config
    program_cfg = json.loads(json.dumps(cell.config))
    if a.num_blocks:
        program_cfg["serve"]["num_blocks"] = a.num_blocks
    if a.control == "ut3":
        program_cfg["total_ut_steps"] = reference_cfg["total_ut_steps"] - 1
    if a.control == "shared-cache":
        share_one_cache(reference_cfg["total_ut_steps"],
                        reference_cfg["num_hidden_layers"])
    served = await server.start(program_cfg, a.seeds[0], workdir)
    gen = spec.load_module(root, "generators", cell.traffic["generator"])
    ref = spec.load_module(root, "reference", reference_cfg["reference"])
    forward = jax.jit(ref.make_forward(reference_cfg))
    rejected = 0
    try:
        for i, seed in enumerate(a.seeds):
            if i:
                served.core.params = None
                served.core.params = server.make_params(served.model, seed, None)
            ref_params = served.core.params
            if a.control == "int8-weights":
                served.core.params = served.model.quantize_params(ref_params)
            jax.block_until_ready(served.core.params)
            t = time.monotonic()
            v = await one_seed(served, ref_params, reference_cfg, settings,
                               seed, root, gen, forward)
            rejected += not v["ok"]
            print(json.dumps({"control": a.control, "seed": seed,
                              "seconds": time.monotonic() - t, **v}), flush=True)
            del ref_params
    finally:
        await served.stop()
    want = 0 if a.control == "none" else len(a.seeds)
    print(f"# {a.control}: rejected {rejected} of {len(a.seeds)} seeds "
          f"(must be {want})", flush=True)
    return 0 if rejected == want else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--control", choices=CONTROLS, required=True)
    p.add_argument("--seeds", type=lambda s: [int(x) for x in s.split(",")],
                   required=True)
    p.add_argument("--num-blocks", type=int, default=0)
    p.add_argument("--workload", default="ouro-2.6b.reason-closed")
    p.add_argument("--root", default=str(spec.REPO_ROOT))
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args(argv)
    root = Path(a.root).resolve()
    cell = spec.load_cell(root, a.workload)
    settings = spec.load_settings(root)
    runner.require_devices(cell.chips, a.rehearse)
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()
    runner.quiet_compile_logs()
    workdir = tempfile.mkdtemp(prefix="ouro-controls-")
    try:
        return asyncio.run(main_async(a, root, cell, settings, workdir))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
