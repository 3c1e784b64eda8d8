"""Standalone Pallas kernel probes on the real backend.

Compiles each kernel variant (bf16 / int8-KV x decode / prefill / mq) at
a representative serving geometry and prints PASS/FAIL with the full
Mosaic error — the fast iteration loop for kernel lowering issues that
interpret-mode tests cannot catch (round 4 found two: partial-tile scale
DMA slices, and the prefill kernel's sublane-indexed q/out slices).

Probe INPUTS come from ``ops/pallas/registry.py``'s ``probe_*_inputs``
builders — the same tensors chip_smoke.py's kernel checks and the kernel
plane's interpret audits consume — so a kernel this sweep exercises is
by construction one the registry knows (``dynamo-tpu lint --kern``'s
KN006 census flags any registered kernel that loses probe coverage).

Usage:  python benchmarks/probe_kernels.py [bf16|int8|all] [8b|1b|probe]
        python benchmarks/probe_kernels.py lengths [out.json]   # decode sweep
        python benchmarks/probe_kernels.py experts [out.json [cell:call,...]]  # grouped matmul
        python benchmarks/probe_kernels.py state [out.json]     # the two recurrent states' decode steps
        python benchmarks/probe_kernels.py dense [out.json]     # dense latent decode by sharers a document
        python benchmarks/probe_kernels.py question [out.json]  # a prefill chunk's two attention forms over a selection
        python benchmarks/probe_kernels.py indexer [out.json]   # a decode step's index scores: the keys gathered, and scored in place
"""

from __future__ import annotations

import functools
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GEOMS = {
    # h, hk, d, batch, max_len, bs, s_prefill
    "probe": dict(h=8, hk=4, d=64, batch=1, max_len=160, bs=16, s=128),
    "1b": dict(h=32, hk=8, d=64, batch=64, max_len=2048, bs=32, s=512),
    "8b": dict(h=32, hk=8, d=128, batch=64, max_len=1024, bs=32, s=512),
}



def time_topk() -> None:
    """Time the three top-k paths at serving shape [64, 128256] — decides
    whether the dual approx/exact sampler design can collapse to
    always-exact (run: probe_kernels.py topk)."""
    import time

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.sampling import _exact_top_k_tiled

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128256), jnp.float32)
    jax.block_until_ready(x)
    paths = {
        "approx_max_k": jax.jit(lambda a: jax.lax.approx_max_k(
            a, 64, recall_target=0.95)),
        "exact_tiled": jax.jit(lambda a: _exact_top_k_tiled(a, 64)),
        "lax_top_k": jax.jit(lambda a: jax.lax.top_k(a, 64)),
    }
    for name, fn in paths.items():
        jax.block_until_ready(fn(x))  # compile
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(x)
        jax.block_until_ready(out)
        print(f"topk/{name}: {(time.perf_counter() - t0) / 20 * 1e3:.3f} ms")


# The decode kernel alone at the benchmark cells' geometries (rows, query
# heads, kv heads; head_dim 128, block 32, a table of 128 blocks) with the
# tiling ``decode_tiling`` gives each, and others (G, C[, R]) to read it by:
# the rule's chunk with one sequence an update (R 1: a branch a row, as
# every kernel until PR 47 had it), at 256 and 512 lanes the (8, 4) every
# geometry had until then, so one run prints the row-chunk of 4 blocks
# beside the one sized by its bytes, and the batched update beside both.
DECODE_GEOMS = {
    "mistral-7b 32x32x1024": (32, 32, 8),
    "mistral-7b-tp4 shard 64x8x256": (64, 8, 2),
    "ouro-2.6b 16x16x2048": (16, 16, 16),
    "qwen3-30b-a3b 32x32x512": (32, 32, 4),
}
DECODE_TILINGS = {"ouro-2.6b 16x16x2048": [(8, 2, 1), (4, 2)],
                  "mistral-7b 32x32x1024": [(8, 4, 1), (4, 4)],
                  "mistral-7b-tp4 shard 64x8x256": [(8, 16, 1), (8, 4, 1)],
                  "qwen3-30b-a3b 32x32x512": [(8, 8, 1), (8, 4, 1)]}


def decode_length_mixes(rows: int, rng) -> dict:
    """Context lengths by slot: equal, uniform, and chat's (cellbench's chat
    cells: prompt lognormal median 512 sigma 0.8 in [32, 3072] plus a
    uniform part of an answer lognormal median 128 sigma 0.6 in [16, 512])
    with every slot live and with a quarter of them."""
    import numpy as np

    prompt = np.clip(rng.lognormal(np.log(512), 0.8, rows), 32, 3072)
    answer = np.clip(rng.lognormal(np.log(128), 0.6, rows), 16, 512)
    chat = (prompt + rng.uniform(0, 1, rows) * answer).astype(np.int32)
    live = np.zeros(rows, bool)
    live[rng.permutation(rows)[:rows // 4]] = True
    return {
        "equal 224": np.full(rows, 224, np.int32),
        "uniform 64-384": rng.integers(64, 385, rows).astype(np.int32),
        "chat, all slots live": chat,
        "chat, 25% live": np.where(live, chat, 0).astype(np.int32),
    }


def profiled_device_ns(fn, args, calls: int) -> dict:
    """{line name: [(event name, duration ns)]} of the first TPU's ``XLA
    Ops`` and ``XLA Modules`` lines over ``calls`` calls of ``fn`` under the
    profiler, after one call outside it.  An op event's name is its HLO
    instruction: a custom call's begins with the kernel's name (its users
    only mention it)."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
        data = ProfileData.from_file(path)
    return {line.name: [(e.name, e.duration_ns) for e in line.events]
            for p in data.planes if p.name == "/device:TPU:0"
            for line in p.lines if line.name in ("XLA Ops", "XLA Modules")}


def time_decode_lengths(out_path: str | None) -> None:
    """µs a call of ``paged_decode_attention_mq`` (the custom call's own
    device time, from a profile of 20 calls) and GB/s of the bytes the
    contexts hold, rows in slot order and grouped by length.  (Run with
    this file copied over the parent of PR 40, the same table reads the
    kernel that fetched every row up to its group's longest.)"""
    import inspect
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas import decode_attention as da
    from dynamo_tpu.ops.pallas.registry import decode_tiling

    d, bs, m, calls = 128, 32, 128, 20
    # a kernel from before PR 47 (this file copied over its checkout) takes
    # one sequence an update and has no word for it
    batches = "seqs_per_update" in inspect.signature(
        da.paged_decode_attention_mq.__wrapped__).parameters
    print(f"# device {jax.devices()[0].device_kind}")

    def kernel_us(fn, args) -> float:
        took = [ns for name, ns in
                profiled_device_ns(fn, args, calls)["XLA Ops"]
                if name.startswith("%paged_decode_attention")]
        assert len(took) == calls, (len(took), calls)
        return float(np.median(took)) / 1e3

    table = []
    for geom, (rows, h, hk) in DECODE_GEOMS.items():
        hkd = hk * d
        rng = np.random.default_rng(40)
        mixes = decode_length_mixes(rows, rng)
        tilings = list(dict.fromkeys(
            [decode_tiling(h, hkd, bs)] + DECODE_TILINGS.get(geom, [])))
        n = max(int((-(-x // bs)).sum()) for x in mixes.values()) + 1
        kq, kc = jax.random.split(jax.random.key(40))
        cache = jax.random.normal(kc, (1, n, 2, bs, hkd), jnp.bfloat16)
        q = jax.random.normal(kq, (rows, 1, h, d), jnp.bfloat16)
        for mix, lens in mixes.items():
            # every row its own blocks, scattered over the pool; block 0
            # (what an empty slot's table of zeros names) is no row's
            need = -(-lens // bs)
            pool = rng.permutation(n - 1)[:need.sum()] + 1
            bt = np.zeros((rows, m), np.int32)
            for r, at in enumerate(np.cumsum(need) - need):
                bt[r, :need[r]] = pool[at:at + need[r]]
            useful = 2 * (2 * hkd * int(lens.sum())
                          + 2 * h * d * int((lens > 0).sum()))
            grouped = np.argsort(-lens, kind="stable")
            for g, c, *r in tilings:
                kw = dict(seqs_per_group=g, blocks_per_chunk=c)
                if r and batches:  # sequences an update; else the kernel's
                    kw["seqs_per_update"] = r[0]
                elif r and r[0] != 1:
                    continue
                fn = jax.jit(lambda q, cache, bt, lens, kw=kw:
                             da.paged_decode_attention_mq(
                                 q, cache, jnp.int32(0), bt, lens, lens - 1,
                                 **kw))
                row = {"geometry": geom, "lengths": mix, "g": g, "c": c,
                       "r": r[0] if r else None, "useful_bytes": useful}
                for label, o in (("slot_order", np.arange(rows)),
                                 ("by_length", grouped)):
                    us = kernel_us(fn, (q[o], cache, jnp.asarray(bt[o]),
                                        jnp.asarray(lens[o])))
                    row[f"{label}_us"] = round(us, 2)
                    row[f"{label}_gb_s"] = round(useful / us / 1e3, 1)
                table.append(row)
                print(json.dumps(row), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(table, f, indent=1)


# The experts' grouped matmul alone at the MoE cells' shapes: (router's
# experts, experts held here, top-k, Dm, F) and the dispatches a cell runs
# (tokens, of which live: an idle decode slot is token 0 at position 0, so
# all idle rows pick the same experts).
EXPERT_GEOMS = {
    "qwen3-30b-a3b": dict(router=128, held=128, k=8, dm=2048, f=768,
                          calls={"decode": (32, 3), "chunk": (512, 512)}),
    "solar-open2-ep16": dict(router=320, held=20, k=8, dm=4096, f=1280,
                             calls={"decode": (64, 64), "chunk": (512, 512)}),
    "glm-5.2-ep16": dict(router=256, held=16, k=8, dm=6144, f=2048,
                         calls={"decode": (32, 32), "question": (128, 128),
                                "chunk": (2048, 2048)}),
    "mistral-small-4-ep8": dict(router=128, held=16, k=4, dm=4096, f=2048,
                                calls={"decode": (32, 32),
                                       "question": (256, 256),
                                       "chunk": (2048, 2048)}),
}
# rows an expert for the threshold's sweep, every held expert alike
EXPERT_ROWS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)


def expert_group_sizes(rng, tokens: int, live: int, router: int, held: int,
                       k: int):
    """Rows each held expert gets when ``live`` tokens pick k distinct
    experts of ``router`` at random and the other tokens all pick the same
    k: int32 [held] (the held experts are the first ``held``)."""
    import numpy as np

    picks = [rng.permutation(router)[:k] for _ in range(live)]
    picks += [rng.permutation(router)[:k]] * (tokens - live)
    flat = np.concatenate(picks)
    return np.bincount(flat[flat < held], minlength=held).astype(np.int32)


def time_experts(out_path: str | None, only: str | None = None) -> None:
    """us a call of the grouped matmul (the custom call's own device time,
    median of 20 in a profile) and GB/s of the touched experts' weights, the
    kernel beside ``lax.ragged_dot``, both reading layer L - 1 of a stacked
    [L, E, K, N] array in place: each cell's dispatches, a decode's also at
    the two next-narrower slices of N, and rows an expert from 1 to 1,024 at
    Qwen3's and GLM's expert sizes (the sweep behind
    ``GROUPED_MATMUL_MAX_ROWS_PER_GROUP``).  ``only``: the dispatches to
    time, ``cell:call,...``, and no sweep."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas.grouped_matmul import (
        grouped_expert_matmul, grouped_matmul_plan,
    )
    from dynamo_tpu.ops.pallas.registry import (
        grouped_matmul_row_tile, grouped_matmul_tiling,
    )

    layers, calls = 2, 20
    print(f"# device {jax.devices()[0].device_kind}")

    def device_us(fn, args, prefix, ops_a_call=1) -> tuple[float, float]:
        """(the named operations, the whole program) a call, in us."""
        lines = profiled_device_ns(fn, args, calls)
        ops = [ns for name, ns in lines["XLA Ops"] if name.startswith(prefix)]
        programs = [ns for _, ns in lines["XLA Modules"]]
        assert len(ops) == calls * ops_a_call, (prefix, len(ops))
        return (float(np.median(ops)) * ops_a_call / 1e3,
                float(np.median(programs[-calls:])) / 1e3)

    def measure(row, sizes, m, k, n, stacks, slices):
        """One shape over ``stacks`` weight arrays (gate and up: 2, one call
        of the kernel and two of ragged_dot; down: 1): ragged_dot, then the
        kernel at each slice of N a weight block."""
        e = sizes.shape[0]
        touched = int((sizes > 0).sum())
        weight_bytes = stacks * touched * k * n * 2
        kx, *kws = jax.random.split(jax.random.key(50), 1 + stacks)
        xs = jax.random.normal(kx, (m, k), jnp.bfloat16)
        ws = tuple(jax.random.normal(kw, (layers, e, k, n), jnp.bfloat16)
                   for kw in kws)
        li = jnp.int32(layers - 1)
        gs = jnp.asarray(sizes)

        def xla(xs, ws, gs, li):
            full = jax.lax.dynamic_update_slice(
                jnp.zeros(layers * e, jnp.int32), gs, (li * e,))
            return [jax.lax.ragged_dot(xs, w.reshape(layers * e, k, n), full)
                    for w in ws]

        row = dict(row, m=m, k=k, n=n, stacks=stacks, touched=touched,
                   weight_mb=round(weight_bytes / 1e6, 1))
        us, whole = device_us(jax.jit(xla), (xs, ws, gs, li),
                              "%ragged-dot-none", stacks)
        row["ragged_dot_us"] = round(us, 1)
        row["ragged_dot_program_us"] = round(whole, 1)
        row["ragged_dot_gb_s"] = round(weight_bytes / us / 1e3, 1)
        want = np.asarray(jax.jit(xla)(xs, ws, gs, li)[-1], np.float32)
        rows = int(sizes.sum())
        tm = grouped_matmul_row_tile(m, max(k, n))
        for tn in slices:
            def kernel(xs, ws, gs, li, tn=tn):
                plan = grouped_matmul_plan(gs, m, tm)
                return grouped_expert_matmul(
                    xs, tuple(w.reshape(layers * e, k, n) for w in ws),
                    plan, li * e, tm=tm, tn=tn)
            fn = jax.jit(kernel)
            got = np.asarray(fn(xs, ws, gs, li)[-1], np.float32)
            worst = float(np.abs(got[:rows] - want[:rows]).max())
            us, whole = device_us(fn, (xs, ws, gs, li),
                                  "%grouped_expert_matmul")
            out = dict(row, tm=tm, tn=tn,
                       kernel_us=round(us, 1),
                       kernel_program_us=round(whole, 1),
                       kernel_gb_s=round(weight_bytes / us / 1e3, 1),
                       worst_abs_diff=round(worst, 4))
            table.append(out)
            print(json.dumps(out), flush=True)

    def rule(m, k, n, stacks):
        return grouped_matmul_tiling(grouped_matmul_row_tile(m, max(k, n)),
                                     k, n, weights=stacks)

    def slices_round(m, k, n, stacks):
        """The registry's slice of N, then the two next narrower."""
        tn = rule(m, k, n, stacks)
        slices = [t for t in range(128, n + 1, 128) if n % t == 0]
        at = slices.index(tn)
        return [tn] + [t for t in slices[max(at - 2, 0):at]
                       if k * t * 2 >= 1 << 20]

    table: list = []
    rng = np.random.default_rng(50)
    for cell, g in EXPERT_GEOMS.items():
        for call, (tokens, live) in g["calls"].items():
            if only and f"{cell}:{call}" not in only.split(","):
                continue
            sizes = expert_group_sizes(rng, tokens, live, g["router"],
                                       g["held"], g["k"])
            m = tokens * g["k"]
            for proj, k, n, stacks in (("gate+up", g["dm"], g["f"], 2),
                                       ("down", g["f"], g["dm"], 1)):
                measure({"cell": cell, "call": call, "proj": proj}, sizes, m,
                        k, n, stacks, slices_round(m, k, n, stacks)
                        if call == "decode" else [rule(m, k, n, stacks)])
    for cell in () if only else ("qwen3-30b-a3b", "glm-5.2-ep16"):
        g = EXPERT_GEOMS[cell]
        for per in EXPERT_ROWS:
            sizes = np.full(g["held"], per, np.int32)
            m = per * g["held"]
            measure({"cell": cell, "call": f"rows {per}", "proj": "gate+up"},
                    sizes, m, g["dm"], g["f"], 2,
                    [rule(m, g["dm"], g["f"], 2)])
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(table, f, indent=1)


def time_state(out_path: str | None) -> None:
    """ms a call of a recurrent state's decode step over the slot array at
    its cell's shape (64 slots, layer 1 of a two-layer leaf; the operations'
    own device time, median of 20 in a profile) and GB/s of the state's one
    read and one write, the kernel beside the XLA form the model ran before
    it (slice, step, ``where(alive)``, set, the leaf donated): the
    state-space update at granite-4.0-h-small's 128 heads of 64 x 128 at
    every tiling of its heads, and the delta rule's at Solar-Open2's 64
    heads of 128 x 128.  Every slot alive, then every eighth idle."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import linear_state, ssm_state
    from dynamo_tpu.ops.pallas import linear_state as delta_kernel
    from dynamo_tpu.ops.pallas import ssm_state as ssm_kernel
    from dynamo_tpu.ops.pallas import registry as reg

    slots, calls = 64, 20
    print(f"# device {jax.devices()[0].device_kind}")

    def xla_form(step):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def fn(state, layer, *rest):
            *vectors, fresh, alive = rest
            old = state[layer]
            y, new = step(*vectors,
                          jnp.where(fresh[:, None, None, None], 0, old))
            new = jnp.where(alive[:, None, None, None], new, old)
            return y, state.at[layer].set(new)
        return fn

    def measure(label, fn, args, state_bytes, kw):
        """The leaf is donated: each call takes the last one's."""
        held = [jnp.array(args[0])]

        def call(*rest):
            y, held[0] = fn(held[0], *rest, **kw)
            return y

        lines = profiled_device_ns(call, args[1:], calls)
        programs = [ns for _, ns in lines["XLA Modules"]][-calls:]
        ops: dict = {}
        for name, ns in lines["XLA Ops"]:    # "%fusion.1 = f32[...] fusion(..."
            ops.setdefault(name.split(" = ")[0], []).append(ns)
        ms = float(np.median(programs)) / 1e6
        largest = sorted(((float(np.median(v)) / 1e6, k)
                          for k, v in ops.items()), reverse=True)[:3]
        row = {"what": label, "program_ms": round(ms, 4),
               "gb_s": round(2 * state_bytes / ms / 1e6, 1),
               "largest_ops_ms": [(k, round(v, 4)) for v, k in largest]}
        print(json.dumps(row), flush=True)
        return row, held[0]

    rows = []
    for idle in (False, True):
        a = list(reg.probe_ssm_state_inputs(2, slots, 128, 64, 128, 1))
        d = list(reg.probe_linear_state_inputs(2, slots, 64, 128))
        if not idle:
            a[-1] = d[-1] = jnp.ones((slots,), bool)
        tag = "7/8 alive" if idle else "all alive"
        nbytes = int(a[-1].sum()) * 128 * 64 * 128 * 4
        row, want = measure(f"ssm xla, {tag}", xla_form(ssm_state.ssd_step),
                            a, nbytes, {})
        rows.append(row)
        for group in (16, 32, 64):
            row, got = measure(
                f"ssm kernel, {group} heads a step, {tag}",
                ssm_kernel.state_update, a, nbytes,
                {"heads_per_step": group})
            row["state_max_abs_diff_from_xla"] = float(
                jnp.abs(got - want).max())
            print(f"#   state after {calls + 1} steps, max |kernel - xla| "
                  f"{row['state_max_abs_diff_from_xla']:.3g}")
            rows.append(row)
        del want, got
        rows.append(measure(f"delta xla, {tag}",
                            xla_form(linear_state.delta_rule_step), d,
                            nbytes, {})[0])
        rows.append(measure(f"delta kernel, {tag}",
                            delta_kernel.state_update, d, nbytes, {})[0])
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "rows": rows}, f, indent=1)


def time_dense_decode(out_path: str | None) -> None:
    """µs a call of ``mla_dense_decode`` alone (the custom call's device
    time, from a profile of 10 calls) at ``mistral-small-4-ep8``'s shapes —
    32 rows of 32 heads, rows of 384 lanes, blocks of 32, a table of 1,152 —
    with 1 / 2 / 3 / 4 / 5 rows asking each document of 24,576 rows and a
    tail of 300 rows of its own each, then the cell's own mix (32 rows on 12
    documents of 16-32 k); at G = 1 (every row alone: the kernel before
    PR 59), 2, 4 and 8 with chunks of 16 blocks, and at G = 8 with chunks of
    32.  Beside it the chunks scored stacked and alone, µs a stacked chunk
    (the call less its lone chunks at the G = 1 run's µs a chunk), GB/s of
    the rows fetched, and the largest |Δ| from G = 1's output."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas import mla_dense_attention as dense

    rows, h, wd, dv, bs, m, calls = 32, 32, 384, 256, 32, 1152, 10
    doc, tail = 24576, 300
    print(f"# device {jax.devices()[0].device_kind}")
    rng = np.random.default_rng(59)
    n = rows * (doc // bs + 16) + 1
    kq, kc = jax.random.split(jax.random.key(59))
    cache = jax.random.normal(kc, (n, bs, wd), jnp.bfloat16)
    q = jax.random.normal(kq, (rows, h, wd), jnp.bfloat16) * 0.05

    def tables(docs: np.ndarray, of: np.ndarray, own: np.ndarray):
        """Block tables of rows that ask document ``of`` [rows] (``docs``:
        its length) and add ``own`` rows each: a document's blocks are one
        list for all its rows, the rest is each row's."""
        free = iter(rng.permutation(n - 1) + 1)
        held = [[next(free) for _ in range(d // bs)] for d in docs]
        bt = np.zeros((rows, m), np.int32)
        lens = (docs[of] // bs * bs + own).astype(np.int32)
        for r in range(rows):
            ids = held[of[r]] + [next(free) for _ in range(-(-own[r] // bs))]
            bt[r, :len(ids)] = ids
        return bt, lens

    cases = {f"{k} a document": tables(
        np.full(-(-rows // k), doc), np.arange(rows) // k,
        np.full(rows, tail)) for k in (1, 2, 3, 4, 5)}
    docs = rng.integers(16384, 32769, 12)
    cases["the cell: 12 documents"] = tables(
        docs, rng.integers(0, 12, rows), rng.integers(160, 301, rows))
    table = []
    for case, (bt, lens) in cases.items():
        alone, us_alone = {}, {}
        for c, g in ((16, 1), (16, 2), (16, 4), (16, 8), (32, 1), (32, 8)):
            fn = jax.jit(functools.partial(
                dense.mla_dense_decode, dv=dv, blocks_per_chunk=c,
                group_rows=g))
            args = (q, cache, jnp.asarray(bt), jnp.asarray(lens))
            out = np.asarray(fn(*args))
            took = [ns for name, ns in
                    profiled_device_ns(fn, args, calls)["XLA Ops"]
                    if name.startswith("%mla_dense_decode")]
            assert len(took) == calls, (len(took), calls)
            us = float(np.median(took)) / 1e3
            groups = dense.decode_groups(np, bt, lens, bs, c, g)
            count, shared = groups[:, 0], groups[:, 1] // c
            lone = int((-(-(-(-lens // bs)) // c)).sum() - count @ shared)
            if g == 1:
                alone[c], us_alone[c] = out, us / lone
            fetched = dense.decode_rows_fetched(
                bt, lens, bs, blocks_per_chunk=c, group_rows=g)
            row = {"case": case, "c": c, "g": g, "us": round(us, 1),
                   "context_rows": int(lens.sum()), "fetched_rows": fetched,
                   "stacked_chunks": int(shared.sum()), "lone_chunks": lone,
                   "us_a_stacked_chunk": round(
                       (us - lone * us_alone[c]) / shared.sum(), 3)
                   if shared.sum() else None,
                   "us_a_lone_chunk": round(us_alone[c], 3),
                   # a row's output is the same whoever shares its group
                   "differs_from_alone": float(
                       np.abs(out - alone[c]).max()),
                   "fetched_gb_s": round(fetched * wd * 2 / us / 1e3, 1)}
            table.append(row)
            print(json.dumps(row), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(table, f, indent=1)


def time_question(out_path: str | None) -> None:
    """The two forms of a GLM-5.2 prefill chunk's attention over a selection
    (64 heads, rows of 576 in 640 lanes, 2,048 selected), each alone, µs a
    call from a profile of 10 calls: ``mla_sparse_prefill_masked`` at a
    question's shapes — the whole padded (256, 33,280) shape and what exists
    of it, 160 tokens over 24,700 rows; the 64 bucket behind a 16 k prefix —
    and at a document chunk's, with µs a live grid step and its share of the
    chip's bf16 peak, then the same question at other tiles; the gather
    (``mla_sparse_prefill``) at the same live queries with ns a fetched row;
    and ``latent_cache.masked_attention`` whole (block gather, unpacking,
    bias, kernel) with what it takes beside the kernel.  The numbers behind
    ``registry.MLA_MASKED_TILE_NS`` / ``MLA_SPARSE_ROW_NS``."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import latent_cache
    from dynamo_tpu.ops.paged_attention import sparse_latent_attention
    from dynamo_tpu.ops.pallas.mla_masked_prefill import (
        mla_sparse_prefill_masked,
    )
    from dynamo_tpu.ops.pallas.registry import (
        MLA_MASKED_KEYS_PER_TILE as TK, MLA_MASKED_TOKENS_PER_TILE as TQ,
        mla_masked_cost, probe_mla_masked_inputs, probe_mla_sparse_inputs,
    )

    h, dq, dv, topk, calls = 64, 640, 512, 2048, 10
    print(f"# device {jax.devices()[0].device_kind}")
    table = []

    def device_us(fn, args, prefix):
        lines = profiled_device_ns(fn, args, calls)
        took = [ns for name, ns in lines["XLA Ops"]
                if name.startswith(prefix)]
        assert len(took) == calls, (prefix, len(took))
        whole = [ns for _, ns in lines["XLA Modules"]]
        return float(np.median(took)) / 1e3, float(np.median(whole)) / 1e3

    def emit(row):
        table.append(row)
        print(json.dumps(row), flush=True)

    def masked(label, s, c, live, ctx, tq=TQ, tk=TK):
        fn = jax.jit(functools.partial(
            mla_sparse_prefill_masked, heads=h, dv=dv, sm_scale=1 / 16,
            tokens_per_tile=tq, keys_per_tile=tk))
        try:
            us, _ = device_us(
                fn, probe_mla_masked_inputs(s, c, h, dq, live, ctx),
                "%mla_sparse_prefill_masked")
        except Exception as e:      # a tile the chip's compiler refuses
            emit({"form": "masked", "case": label, "tq": tq, "tk": tk,
                  "error": str(e).splitlines()[0][:200]})
            return
        steps = -(-live // tq) * -(-ctx // tk)
        cost = mla_masked_cost(s, c, h, dq, dv, tq, tk, live, ctx)
        emit({"form": "masked", "case": label, "s": s, "c": c, "live": live,
              "ctx": ctx, "tq": tq, "tk": tk, "us": round(us, 1),
              "live_steps": steps, "us_a_live_step": round(us / steps, 3),
              "us_a_live_token": round(us / live, 2),
              "peak_pct": round(cost["flops"] / us / 197e6 * 100, 1)})

    masked("question 256, whole padded shape", 256, 33280, 256, 33280)
    masked("question 256, 160 tokens over 24,700", 256, 33280, 160, 24700)
    masked("question 64, whole padded shape", 64, 16896, 64, 16896)
    masked("question 64, 50 tokens over 12,000", 64, 16896, 50, 12000)
    masked("chunk 2,048 at 34,816", 2048, 34816, 2048, 34816)
    masked("chunk 2,048 at 18,432 of 34,816", 2048, 34816, 2048, 18432)
    for tq, tk in ((16, 256), (8, 512), (16, 1024), (32, 512)):
        masked("question 256, 160 tokens over 24,700", 256,
               -(-33024 // tk) * tk, 160, 24700, tq, tk)

    for n, live in ((256, 160), (64, 50)):
        lens = np.where(np.arange(n) < live, topk, 0)
        fn = jax.jit(functools.partial(
            sparse_latent_attention, sm_scale=1 / 16, phase="prefill"))
        us, whole = device_us(
            fn, probe_mla_sparse_inputs(n, h, 576, topk, 1 << 16, lens),
            "%mla_sparse_prefill")
        emit({"form": "gather", "n": n, "live": live, "rows": live * topk,
              "us": round(us, 1), "ns_a_row": round(us * 1e3 / live / topk, 2),
              "us_a_live_token": round(us / live, 2),
              "us_whole_call": round(whole, 1)})

    # the masked form as the model calls it: the blocks gathered and
    # unpacked, the mask turned into the bias, the kernel
    bs, blocks = 32, 2048
    rng = np.random.default_rng(65)
    latent = jnp.asarray(
        rng.integers(0, 1 << 30, (1, blocks, bs, 1, 384)) & 0x3FFF3FFF,
        jnp.uint32)
    for s, c, live, ctx in ((256, 33024, 160, 24700), (64, 16448, 50, 12000)):
        bt = jnp.asarray(rng.permutation(blocks)[None, :c // bs], jnp.int32)
        mask = np.tril(np.ones((s, c), bool), k=ctx - live)
        mask &= rng.random((s, c)) < 0.1
        mask[live:] = False
        mask[:, ctx:] = False
        q = jnp.asarray(rng.normal(size=(1, s, h, 576)) * 0.1, jnp.bfloat16)
        fn = jax.jit(lambda *a: latent_cache.masked_attention(
            *a[:5], 1 / 16, dv, *a[5:]))
        args = (q, latent, jnp.int32(0), bt, jnp.asarray(mask)[None],
                jnp.asarray([live], jnp.int32), jnp.asarray([ctx], jnp.int32))
        us, whole = device_us(fn, args, "%mla_sparse_prefill_masked")
        emit({"form": "masked_attention", "s": s, "c": c, "live": live,
              "ctx": ctx, "us_kernel": round(us, 1),
              "us_whole_call": round(whole, 1),
              "us_beside_the_kernel": round(whole - us, 1)})
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(table, f, indent=1)


def time_indexer(out_path: str | None) -> None:
    """µs a call of the indexer's decode scores at ``glm-5.2-ep16``'s shapes —
    32 rows of 32 heads of 128, blocks of 32 keys, a table of 1,152 blocks —
    in both forms: the keys of every row's whole table gathered and scored by
    XLA (``index_scores`` over ``keys[tables]``, jitted alone: the program's
    time on the ``XLA Modules`` line), and ``dsa_index_scores`` (the custom
    call's own time) by chunk size C and group cap G.  Cases: the cell's mix
    (32 rows on 12 documents, four each of 16,384 / 24,576 / 32,768 keys, row
    k on document k mod 12, 160-300 keys of its own), every row alone on a
    document of its own, and 2 / 3 / 4 rows a document of 24,576.  Beside the
    times: keys fetched over the table's positions, the largest |Δ| from the
    XLA form over the positions a row sees, and whether a row's scores are
    the same bits as at G = 1."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.glm_dsa import index_scores
    from dynamo_tpu.ops.pallas import dsa_index_scores as dsa
    from dynamo_tpu.ops.pallas.registry import probe_dsa_index_inputs

    rows, h, d, bs, m, calls = 32, 32, 128, 32, 1152, 10
    print(f"# device {jax.devices()[0].device_kind}")
    rng = np.random.default_rng(67)
    own = rng.integers(160, 301, rows)
    cases = {"the cell: 12 documents": (
        np.repeat([16384, 24576, 32768], 4), np.arange(rows) % 12, own)}
    for k in (1, 2, 3, 4):
        cases[f"{k} a document of 24,576"] = (
            np.full(-(-rows // k), 24576), np.arange(rows) // k, own)

    @jax.jit
    def gathered(q, w, keys, bt, lens):
        return index_scores(q[:, None], w[:, None],
                            keys[bt].reshape(rows, m * bs, d))[:, 0]

    table = []
    for case, (docs, of, own) in cases.items():
        args = probe_dsa_index_inputs(rows, h, d, bs, m, docs, of, own,
                                      seed=67)
        bt, lens = (np.asarray(a) for a in args[3:])
        seen = np.arange(m * bs)[None, :] < lens[:, None]
        ref = np.where(seen, np.asarray(gathered(*args)), 0.0)
        took = [ns for _, ns in
                profiled_device_ns(gathered, args, calls)["XLA Modules"]]
        row = {"case": case, "form": "gather + index_scores (XLA)",
               "us": round(float(np.median(took)) / 1e3, 1),
               "context_keys": int(lens.sum()),
               "table_keys": rows * m * bs}
        table.append(row)
        print(json.dumps(row), flush=True)
        alone = {}
        for c, g in ((16, 1), (16, 8), (32, 1), (32, 2), (32, 4), (32, 8),
                     (64, 1), (64, 8)):
            fn = jax.jit(functools.partial(
                dsa.dsa_index_scores, blocks_per_chunk=c, group_rows=g))
            out = np.where(seen, np.asarray(fn(*args)), 0.0)
            took = [ns for name, ns in
                    profiled_device_ns(fn, args, calls)["XLA Ops"]
                    if name.startswith(f"%{dsa.KERNEL_NAME}")]
            assert len(took) == calls, (len(took), calls)
            us = float(np.median(took)) / 1e3
            if g == 1:
                alone[c] = out
            fetched = dsa.index_keys_read(bt, lens, bs, c, g)
            row = {"case": case, "form": dsa.KERNEL_NAME, "c": c, "g": g,
                   "us": round(us, 1), "fetched_keys": fetched,
                   "fetched_pct_of_table": round(
                       100.0 * fetched / (rows * m * bs), 2),
                   "fetched_gb_s": round(fetched * d * 2 / us / 1e3, 1),
                   "ns_a_block": round(us * 1e3 / (fetched / bs), 1),
                   "max_abs_delta_from_xla": float(np.abs(out - ref).max()),
                   "same_bits_as_alone": bool((out == alone[c]).all())}
            table.append(row)
            print(json.dumps(row), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(table, f, indent=1)


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which == "indexer":
        time_indexer(sys.argv[2] if len(sys.argv) > 2 else None)
        return
    if which == "question":
        time_question(sys.argv[2] if len(sys.argv) > 2 else None)
        return
    if which == "dense":
        time_dense_decode(sys.argv[2] if len(sys.argv) > 2 else None)
        return
    if which == "state":
        time_state(sys.argv[2] if len(sys.argv) > 2 else None)
        return
    if which == "topk":
        time_topk()
        return
    if which == "experts":
        time_experts(*sys.argv[2:4])
        return
    if which == "lengths":
        time_decode_lengths(sys.argv[2] if len(sys.argv) > 2 else None)
        return
    geom = GEOMS[sys.argv[2] if len(sys.argv) > 2 else "8b"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_attention_mq,
    )
    from dynamo_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention, ragged_paged_prefill_attention,
    )
    from dynamo_tpu.ops.pallas.registry import (
        probe_decode_inputs, probe_int8_matmul_inputs, probe_prefill_inputs,
        probe_ragged_inputs,
    )

    h, hk, d, batch, max_len, bs, s = (
        geom["h"], geom["hk"], geom["d"], geom["batch"], geom["max_len"],
        geom["bs"], geom["s"])
    m = -(-max_len // bs)
    n = min(batch * m + 4, 4096)
    lens = np.full((batch,), min(4 * bs, max_len), np.int32)

    def probe(label, fn):
        try:
            out = fn()
            jax.block_until_ready(out)
            print(f"PASS {label}")
            return True
        except Exception as e:
            msg = str(e)
            print(f"FAIL {label}: {type(e).__name__}")
            print("\n".join(msg.splitlines()[:30]))
            if os.environ.get("DYNAMO_PROBE_TRACE"):
                traceback.print_exc()
            return False

    def unified_inputs(quant: bool):
        # unified mixed dispatch: a DECODE row (1 fresh token, start NOT
        # block-aligned — the full-cached-prefix DMA path) ahead of a
        # block-aligned prefill span on the same flat axis; the builder
        # supplies tensors, only the row layout is overridden here
        args = list(probe_ragged_inputs(bs + s, 2, h, hk, d, bs, n, m,
                                        quant=quant))
        args[6:9] = [jnp.asarray([2 * bs + 3 + 1, s], jnp.int32),  # seq_lens
                     jnp.asarray([2 * bs + 3, 0], jnp.int32),      # starts
                     jnp.asarray([0, bs], jnp.int32)]              # roff
        return args

    variants = []
    for mode in (["bf16", "int8"] if which == "all" else [which]):
        q8 = mode == "int8"
        variants += [
            (f"decode/{mode}", lambda q8=q8: paged_decode_attention(
                *probe_decode_inputs(batch, h, hk, d, bs, n, m, lens,
                                     quant=q8))),
            (f"mq/{mode}", lambda q8=q8: paged_decode_attention_mq(
                *probe_decode_inputs(batch, h, hk, d, bs, n, m, lens,
                                     quant=q8, s_q=4))),
            (f"prefill/{mode}", lambda q8=q8: paged_prefill_attention(
                *probe_prefill_inputs(1, s, h, hk, d, bs, n, m, quant=q8))),
            # token-budget ragged prefill: two rows packed on one flat
            # axis, each with a cached prefix (per-row DMA path)
            (f"ragged/{mode}", lambda q8=q8: ragged_paged_prefill_attention(
                *probe_ragged_inputs(s, 2, h, hk, d, bs, n, m, quant=q8))),
            (f"unified/{mode}", lambda q8=q8: ragged_paged_prefill_attention(
                *unified_inputs(q8))),
        ]
    # dequant-in-kernel int8 matmul at decode and prefill row counts
    from dynamo_tpu.ops.pallas.int8_matmul import int8_matmul

    wk, wn = hk * d * (h // hk), 14336  # 8B-ish ffn width
    for rows in (64, 512):
        variants.append((
            f"int8_matmul/m{rows}",
            lambda rows=rows: int8_matmul(
                *probe_int8_matmul_inputs(rows, wk, wn),
                out_dtype=jnp.bfloat16),
        ))
    # grouped-MoE ragged_dot lowering (Mixtral-ish shapes: E=8 experts,
    # 512 routed token-slots, H=4096, F=14336/4 keeps the probe light)
    def moe_ragged():
        e, t, hd_, f = 8, 512, hk * d * (h // hk), 3584
        xs = jnp.ones((t, hd_), jnp.bfloat16)
        w = jnp.ones((e, hd_, f), jnp.bfloat16)
        sizes = jnp.full((e,), t // e, jnp.int32)
        return jax.lax.ragged_dot(xs, w, sizes)

    variants.append(("moe/ragged_dot", moe_ragged))
    # sparse latent attention at GLM-5.2's widths (64 heads, rows of 576)
    # and the DMA movers of its cache; the dispatch picks the kernel
    from dynamo_tpu.ops.pallas.latent_cache_dma import write_rows
    from dynamo_tpu.ops.pallas.registry import (
        probe_latent_dma_inputs, probe_mla_sparse_inputs,
    )
    from dynamo_tpu.ops.paged_attention import sparse_latent_attention

    for phase, nq in (("decode", batch), ("prefill", 256)):
        variants.append((
            f"mla_sparse/{phase}",
            lambda phase=phase, nq=nq: sparse_latent_attention(
                *probe_mla_sparse_inputs(
                    nq, 64, 576, 2048, 1 << 16,
                    np.linspace(1, 4096, nq).astype(np.int32)),
                sm_scale=1 / 16, phase=phase)))
    from dynamo_tpu.ops.pallas.mla_masked_prefill import (
        mla_sparse_prefill_masked,
    )
    from dynamo_tpu.ops.pallas.registry import probe_mla_masked_inputs

    # a chunk of 512 tokens over 4,096 rows, and a question: 160 of 256
    # tokens over 24,700 of 33,280
    for shape in ((512, 4096), (256, 33280, 160, 24700)):
        variants.append((
            f"mla_sparse_prefill_masked/{shape[0]}",
            lambda shape=shape: mla_sparse_prefill_masked(
                *probe_mla_masked_inputs(shape[0], shape[1], 64, 640,
                                         *shape[2:]),
                heads=64, dv=512, sm_scale=1 / 16)))
    from dynamo_tpu.ops.pallas.dsa_index_scores import dsa_index_scores
    from dynamo_tpu.ops.pallas.registry import probe_dsa_index_inputs

    # the indexer's decode scores: 32 rows on 12 documents of 16-32 k keys
    variants.append((
        "dsa_index_scores/decode",
        lambda: dsa_index_scores(*probe_dsa_index_inputs(
            32, 32, 128, 32, 1152, np.repeat([16384, 24576, 32768], 4),
            np.arange(32) % 12, np.full(32, 300)))))
    variants.append((
        "latent_cache/write_rows",
        lambda: write_rows(*probe_latent_dma_inputs(1 << 16, 576, 2048))))
    # the recurrent state's decode step at Solar-Open2's widths (64 heads of
    # 128 x 128 float32, 16 slots of a two-layer leaf: 537 MB)
    from dynamo_tpu.ops.pallas.linear_state import state_update
    from dynamo_tpu.ops.pallas.registry import probe_linear_state_inputs

    variants.append((
        "linear_state/update",
        lambda: state_update(*probe_linear_state_inputs(2, 16, 64, 128))))
    # ... and the state-space state's at granite-4.0-h-small's (128 heads of
    # 64 x 128 float32, one group of B and C: the same 537 MB)
    from dynamo_tpu.ops.pallas import ssm_state as ssm_kernel
    from dynamo_tpu.ops.pallas.registry import probe_ssm_state_inputs

    variants.append((
        "ssm_state/update",
        lambda: ssm_kernel.state_update(
            *probe_ssm_state_inputs(2, 16, 128, 64, 128, 1))))
    # ... and the selective state's two at AI21-Jamba2-3B's (16 numbers a
    # channel, 5,120 channels = 40 register rows: 16 slots' decode step of a
    # two-layer leaf, and one request's 512-token chunk)
    from dynamo_tpu.ops.pallas import selective_state as selective_kernel
    from dynamo_tpu.ops.pallas.registry import (
        probe_selective_scan_inputs, probe_selective_step_inputs,
    )

    variants.append((
        "selective_state/update",
        lambda: selective_kernel.state_update(
            *probe_selective_step_inputs(2, 16, 16, 40))))
    variants.append((
        "selective_state/scan",
        lambda: selective_kernel.state_scan(
            *probe_selective_scan_inputs(2, 16, 16, 40, 512))))
    # the experts' grouped matmul at Solar-Open2's decode shape (512 sorted
    # rows, 32 of them on 20 held experts of 4,096 x 1,280, layer 1 of two)
    from dynamo_tpu.ops.pallas import grouped_matmul as gmm
    from dynamo_tpu.ops.pallas.registry import (
        grouped_matmul_row_tile, probe_grouped_matmul_inputs,
    )

    def experts():
        xs, w, sizes, first = probe_grouped_matmul_inputs(
            512, 2, 20, 4096, 1280, 32)
        tm = grouped_matmul_row_tile(512, 4096)
        plan = gmm.grouped_matmul_plan(sizes, 512, tm)
        return gmm.grouped_expert_matmul(xs, (w, w), plan, first, tm=tm)

    variants.append(("experts/grouped_matmul", experts))
    ok = all([probe(lbl, fn) for lbl, fn in variants])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
