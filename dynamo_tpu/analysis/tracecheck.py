"""Compile-plane static analysis (dttrace): jaxpr/HLO trace audit.

The AST rules (rules_jax.py) and the project pass see *source*; the
runtime sanitizer sees *tasks*.  Neither sees what XLA actually
compiles — yet the costliest TPU bugs only exist at trace/lowering
level: a silent retrace in the decode hot loop (an unbucketed shape or
an unhashed static sneaks into a dispatch), a ``donate_argnums`` buffer
that does not actually alias in the lowered HLO (the whole KV pool gets
copied every step), an f32 upcast on a bf16 hot path (double the HBM
traffic), or a config change that statically cannot fit a chip's HBM.
With hardware down (ROADMAP standing note), these CPU-side compile-level
checks are the only guard on TPU behavior.

This pass registers every jitted serving entrypoint — the five donated
``EngineCore`` impls (incl. the unified mixed prefill+decode dispatch),
the model forwards, the Pallas-backed ops (audited
through their XLA fallback lowerings on CPU) — and, per entrypoint and
per config of a small representative matrix, extracts four fact
families **without running any model math** (``jax.eval_shape`` /
``jax.make_jaxpr`` / ``.lower()`` over ``ShapeDtypeStruct`` args):

- **trace-signature census** — the declared matrix of shape/dtype/static
  signatures the scheduler can produce (prefill buckets × prefix-block
  buckets, spec table slices, ragged token/row buckets).
  The matrix is enumerated twice and hashed; an axis change, an
  unhashed static, or an undeclared signature shows up as drift.  The
  seeded runtime complement (tests/test_tracecheck.py) proves the hot
  loop compiles exactly once per declared bucket.
- **donation audit** — every ``donate_argnums`` leaf must carry a
  ``tf.aliasing_output`` attribute in the lowered module (the
  jaxpr-level complement of AST rule DT103) and must actually be *used*
  by the computation; donated-but-unaliased and dead donations are
  findings.
- **dtype-propagation** — widening ``convert_element_type`` sites
  (bf16/f16/int8 → f32) at or above a hidden-size worth of elements,
  walked recursively through scan/pjit sub-jaxprs.  By-design sites
  (f32 logits, f32 softmax/norm accumulation) carry justifications in
  the manifest; a new site is a finding.
- **static HBM footprint** — params + KV pool + peak temporaries (from
  the jaxpr, donated-shaped outputs excluded as in-place) against a
  per-chip budget, so an OOM-at-deploy config fails in tier-1 instead.

Facts snapshot into the committed ``trace_manifest.json`` with the same
baseline/justification/``--update`` contract as ``baseline.json``:
``dynamo-tpu lint --trace`` exits 1 on any non-accepted finding or any
fact drift, ``--update-baseline`` re-snapshots facts and carries
justifications over by (entrypoint, rule, key).
"""

from __future__ import annotations

import hashlib
import itertools
import json
import re
import warnings
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

__all__ = [
    "DEFAULT_MANIFEST_PATH",
    "TRACE_RULES",
    "TraceFinding",
    "Manifest",
    "Entrypoint",
    "Signature",
    "build_registry",
    "collect_facts",
    "check_facts",
    "run_trace",
]

DEFAULT_MANIFEST_PATH = Path(__file__).parent / "trace_manifest.json"

# Per-chip HBM budget for the representative deployment config.  v5e has
# 16 GiB; the estimate must leave runtime slack (XLA scratch, framework
# overhead, collectives buffers) so the budget is 95% of the chip.
V5E_HBM_BYTES = 16 * (1 << 30)
HBM_BUDGET_FRACTION = 0.95

TRACE_RULES = {
    "TR001": ("entrypoint-drift",
              "registered entrypoint set changed vs the manifest"),
    "TR002": ("signature-drift",
              "declared trace-signature matrix changed vs the manifest"),
    "TR003": ("unstable-trace-key",
              "rebuilding the signature matrix yields different keys "
              "(unhashed static / id-keyed object in a dispatch)"),
    "TR004": ("donated-not-aliased",
              "donate_argnums leaf not aliased in the lowered HLO "
              "(jaxpr-level complement of AST rule DT103)"),
    "TR005": ("dead-donation",
              "donated leaf is never read by the computation"),
    "TR006": ("f32-upcast",
              "widening dtype conversion on a bf16/int8 hot path"),
    "TR007": ("hbm-over-budget",
              "params + KV pool + peak temporaries exceed the per-chip "
              "HBM budget"),
}

_MANIFEST_NOTE = (
    "CPU-derived facts (jax.eval_shape/make_jaxpr/.lower() over "
    "ShapeDtypeStructs; Pallas ops audited via their XLA fallback "
    "lowerings): HBM figures and kernel peaks are compile-plane "
    "estimates, not measurements: any perf-claiming PR lands numbers "
    "from a chip run (PERF.md)."
)


# ---------------------------------------------------------------- findings ----


@dataclass(frozen=True, order=True)
class TraceFinding:
    """One compile-plane finding.  ``key`` is the stable acceptance key:
    (entrypoint, rule, key) matches manifest ``accepted`` entries the
    way (path, rule, content) matches baseline.json entries."""

    entrypoint: str
    rule: str
    key: str
    message: str

    @property
    def accept_key(self) -> tuple[str, str, str]:
        return (self.entrypoint, self.rule, self.key)

    def render(self) -> str:
        return f"{self.entrypoint}: {self.rule}[{self.key}] {self.message}"

    def to_json(self) -> dict:
        return {
            "entrypoint": self.entrypoint,
            "rule": self.rule,
            "key": self.key,
            "message": self.message,
        }


# ---------------------------------------------------------------- manifest ----


class Manifest:
    """Committed compile-plane snapshot + accepted (justified) findings.

    Same contract as core.Baseline: ``accepted`` entries carry a
    one-line justification and are matched as a (entrypoint, rule, key)
    multiset; ``--update-baseline`` (with ``--trace``) re-snapshots the
    facts and carries justifications over where the key still matches.
    """

    def __init__(self, entrypoints: Optional[dict] = None,
                 accepted: Optional[list[dict]] = None,
                 header: Optional[dict] = None):
        self.entrypoints: dict = entrypoints or {}
        self.accepted: list[dict] = accepted or []
        self.header: dict = header or {}

    @classmethod
    def load(cls, path: Path) -> "Manifest":
        if not Path(path).is_file():
            return cls()
        data = json.loads(Path(path).read_text())
        return cls(dict(data.get("entrypoints", {})),
                   list(data.get("accepted", [])),
                   dict(data.get("header", {})))

    def save(self, path: Path) -> None:
        doc = {
            "version": 1,
            "header": self.header or {
                "note": _MANIFEST_NOTE,
                "hbm_budget": {
                    "chip": "v5e",
                    "bytes": int(V5E_HBM_BYTES * HBM_BUDGET_FRACTION),
                },
            },
            "entrypoints": self.entrypoints,
            "accepted": sorted(
                self.accepted,
                key=lambda e: (e["entrypoint"], e["rule"], e["key"]),
            ),
        }
        Path(path).write_text(
            json.dumps(doc, indent=2, sort_keys=True) + "\n"
        )

    def _counts(self) -> dict[tuple[str, str, str], int]:
        counts: dict[tuple[str, str, str], int] = {}
        for e in self.accepted:
            key = (e["entrypoint"], e["rule"], e["key"])
            counts[key] = counts.get(key, 0) + 1
        return counts

    def filter(self, findings: list[TraceFinding]) -> list[TraceFinding]:
        """Findings NOT covered by an accepted entry (stable-sorted)."""
        budget = self._counts()
        fresh: list[TraceFinding] = []
        for f in sorted(findings):
            if budget.get(f.accept_key, 0) > 0:
                budget[f.accept_key] -= 1
            else:
                fresh.append(f)
        return fresh

    @classmethod
    def from_facts(cls, facts: dict, findings: list[TraceFinding],
                   previous: "Manifest") -> "Manifest":
        """Re-snapshot: current facts become the committed entrypoints;
        intrinsic findings become accepted entries, carrying the previous
        justification where (entrypoint, rule, key) still matches."""
        just: dict[tuple[str, str, str], list[str]] = {}
        for e in previous.accepted:
            key = (e["entrypoint"], e["rule"], e["key"])
            just.setdefault(key, []).append(e.get("justification", ""))
        accepted = []
        for f in sorted(findings):
            carried = just.get(f.accept_key)
            accepted.append({
                "entrypoint": f.entrypoint,
                "rule": f.rule,
                "key": f.key,
                "message": f.message,
                "justification": (
                    carried.pop(0) if carried else "TODO: justify"
                ),
            })
        return cls(facts, accepted, previous.header or None)


# ------------------------------------------------------------- entrypoints ----


@dataclass
class Signature:
    """One declared dispatch signature: positional args (pytrees of
    ShapeDtypeStruct) plus static kwargs."""

    label: str
    args: tuple
    statics: dict = field(default_factory=dict)


@dataclass
class Entrypoint:
    """One registered jitted serving entrypoint.

    ``build(**axis_values)`` returns a Signature (or None for an
    invalid axis combination); ``axes`` declares the full matrix the
    scheduler can produce.  ``jit_fn`` (the live jitted callable) is
    lowered for the donation audit; ``raw_fn`` (the unjitted impl) is
    traced for jaxpr-level facts.
    """

    name: str
    axes: dict[str, list]
    build: Callable[..., Optional[Signature]]
    jit_fn: Optional[Callable] = None
    raw_fn: Optional[Callable] = None
    donate_argnums: tuple[int, ...] = ()
    # axis-value dicts to eval_shape / lower (first is the donation rep)
    representatives: list[dict] = field(default_factory=list)
    upcast_min_elems: int = 0  # 0 = skip the dtype audit
    hbm: Optional[Callable[[], dict]] = None


def _sig_key(sig: Signature) -> str:
    """Stable short hash of one dispatch signature: flattened input
    avals + tree structure + sorted statics.  Two dispatches with the
    same key hit the same compiled executable; an unhashable/id-keyed
    static makes the key unstable across rebuilds (TR003)."""
    import jax

    leaves, treedef = jax.tree.flatten(sig.args)
    payload = (
        tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
        str(treedef),
        tuple(sorted((k, repr(v)) for k, v in sig.statics.items())),
    )
    return hashlib.sha256(repr(payload).encode()).hexdigest()[:16]


def enumerate_signatures(ep: Entrypoint) -> dict[str, str]:
    """{label: key} over the declared axis matrix (invalid combos
    skipped)."""
    out: dict[str, str] = {}
    names = sorted(ep.axes)
    for combo in itertools.product(*(ep.axes[n] for n in names)):
        values = dict(zip(names, combo))
        sig = ep.build(**values)
        if sig is None:
            continue
        out[sig.label] = _sig_key(sig)
    return out


def _matrix_hash(signatures: dict[str, str]) -> str:
    payload = tuple(sorted(signatures.items()))
    return hashlib.sha256(repr(payload).encode()).hexdigest()[:16]


# ---------------------------------------------------------------- registry ----


def _pow2s_upto(n: int) -> list[int]:
    out, b = [], 1
    while b <= n:
        out.append(b)
        b *= 2
    return out


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _operands(*host):
    """What ``EngineCore._upload_dispatch`` hands a serving call for host
    arrays of these shapes (``None``: where the impl takes its key; the
    buffer's first word says which of the key block's it is): the one
    buffer's shape, and the static layout that takes it apart."""
    import numpy as np

    from dynamo_tpu.engine import operands

    bufs, layout = operands.pack((np.zeros((), np.int32), tuple(
        a if a is None else np.zeros(a.shape, a.dtype) for a in host), {}))
    return tuple(_sds(b.shape, b.dtype) for b in bufs), layout


def _tiny_model_config():
    from dynamo_tpu.models.config import ModelConfig

    return ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8,
        max_position_embeddings=256, dtype="bfloat16",
    )


def _tiny_engine_config(**kw):
    from dynamo_tpu.engine.config import EngineConfig

    base = dict(
        max_batch_size=4, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    base.update(kw)
    return EngineConfig(**base)


def _engine_entrypoints(tag: str, model_cfg, engine_cfg) -> list[Entrypoint]:
    """The donated EngineCore impls (step / multi-decode / spec-verify /
    ragged-prefill / unified-mixed) under one (model, engine)
    config.  The core is built with shape-only params (eval_shape), so
    registration never materializes weights."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.sampling import K_MAX
    from dynamo_tpu.models.llama import LlamaModel

    model = LlamaModel(model_cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    core = EngineCore(model, params, engine_cfg)
    cfg = engine_cfg
    m = cfg.max_blocks_per_seq
    b = cfg.max_batch_size
    cache = jax.eval_shape(
        lambda: model.init_kv_cache(
            cfg.num_blocks, cfg.block_size,
            cfg.cache_dtype or model_cfg.dtype,
        )
    )
    i32, f32 = jnp.int32, jnp.float32
    keys = _sds(core._keys.shape, core._keys.dtype)
    pb_axis = [0] + _pow2s_upto(m)
    min_elems = model_cfg.hidden_size
    eps: list[Entrypoint] = []

    def keyed(phase, prefix_blocks, span):
        """Whether a prefill dispatch can reach jit with this static
        ``prefix_blocks``: the engine hands over what its dispatch rule
        maps a prefix to (``EngineCore._prefix_blocks``), so a value the
        mapping turns into another names a program it never builds."""
        return core._prefix_blocks(phase, prefix_blocks, span) == prefix_blocks

    def build_step(s_bucket, prefix_blocks):
        if not keyed("prefill", prefix_blocks, s_bucket):
            return None
        bufs, layout = _operands(
            _sds((1, s_bucket), i32), _sds((1, s_bucket), i32),
            _sds((1, m), i32), _sds((1,), i32),
            _sds((1, s_bucket), i32), _sds((1,), i32), None,
            _sds((1,), f32), _sds((1,), i32), _sds((1,), f32))
        return Signature(
            f"s={s_bucket},pb={prefix_blocks}", (params, cache, keys, bufs),
            dict(layout=layout, prefix_blocks=prefix_blocks, k_cand=K_MAX,
                 exact=False),
        )

    eps.append(Entrypoint(
        name=f"engine.step[{tag}]",
        axes={"s_bucket": list(cfg.prefill_buckets),
              "prefix_blocks": pb_axis},
        build=build_step,
        jit_fn=core._step_fn, raw_fn=core._step_fn.__wrapped__,
        donate_argnums=(1,),
        representatives=[
            dict(s_bucket=cfg.prefill_buckets[-1], prefix_blocks=0),
            dict(s_bucket=cfg.prefill_buckets[0], prefix_blocks=pb_axis[-1]),
        ],
        upcast_min_elems=min_elems,
    ))

    def build_multi():
        bufs, layout = _operands(
            _sds((b,), i32), _sds((b,), i32), _sds((b, m), i32),
            _sds((b,), i32), _sds((b,), i32), None,
            _sds((b,), f32), _sds((b,), i32), _sds((b,), f32))
        return Signature(
            "decode", (params, cache, keys, bufs),
            dict(layout=layout, k_cand=K_MAX, exact=False),
        )

    # the one decode program: no axis, one signature
    eps.append(Entrypoint(
        name=f"engine.decode_multi[{tag}]",
        axes={},
        build=build_multi,
        jit_fn=core._multi_fn, raw_fn=core._multi_fn.__wrapped__,
        donate_argnums=(1,),
        representatives=[{}],
        upcast_min_elems=min_elems,
    ))

    if cfg.spec_tokens > 0:
        s = cfg.spec_tokens + 1

        def build_spec(m_used):
            bufs, layout = _operands(
                _sds((b, s), i32), _sds((b, s), i32),
                _sds((b, m_used), i32), _sds((b,), i32),
                _sds((b, s), i32), None,
                _sds((b,), f32), _sds((b,), i32), _sds((b,), f32),
                _sds((b,), f32), _sds((b,), i32), _sds((b,), bool))
            return Signature(f"m_used={m_used}", (params, cache, keys, bufs),
                             dict(layout=layout, k_cand=K_MAX, exact=False))

        eps.append(Entrypoint(
            name=f"engine.spec_verify[{tag}]",
            axes={"m_used": _pow2s_upto(m)},
            build=build_spec,
            jit_fn=core._spec_fn, raw_fn=core._spec_fn.__wrapped__,
            donate_argnums=(1,),
            representatives=[dict(m_used=_pow2s_upto(m)[-1])],
            upcast_min_elems=min_elems,
        ))

    def flat_axis_operands(t_bucket, r_pad):
        """Of a ragged prefill and of a unified dispatch: rows of several
        sequences on one token axis."""
        return _operands(
            _sds((1, t_bucket), i32), _sds((1, t_bucket), i32),
            _sds((r_pad, m), i32), _sds((r_pad,), i32),
            _sds((1, t_bucket), i32), _sds((1, t_bucket), i32),
            _sds((r_pad,), i32), _sds((r_pad,), i32),
            _sds((r_pad,), i32), None,
            _sds((r_pad,), f32), _sds((r_pad,), i32),
            _sds((r_pad,), f32))

    if cfg.prefill_token_budget > 0 and getattr(
            model, "supports_ragged_prefill", False):
        bs = cfg.block_size
        t_max = cfg.bucket_for(cfg.prefill_token_budget)
        t_axis = [t for t in cfg.prefill_buckets if t <= t_max]
        r_axis = _pow2s_upto(1 << max(0, (b - 1).bit_length()))

        def build_ragged(t_bucket, r_pad, prefix_blocks):
            # pow2ceil(r_real) == r_pad needs r_real > r_pad/2 rows, each
            # at least one block wide on the flat axis
            min_rows = r_pad // 2 + 1 if r_pad > 1 else 1
            if min_rows * bs > t_bucket or not keyed(
                    "ragged", prefix_blocks, t_bucket):
                return None
            bufs, layout = flat_axis_operands(t_bucket, r_pad)
            return Signature(
                f"t={t_bucket},r={r_pad},pb={prefix_blocks}",
                (params, cache, keys, bufs),
                dict(layout=layout, prefix_blocks=prefix_blocks,
                     k_cand=K_MAX, exact=False),
            )

        eps.append(Entrypoint(
            name=f"engine.prefill_ragged[{tag}]",
            axes={"t_bucket": t_axis, "r_pad": r_axis,
                  "prefix_blocks": pb_axis},
            build=build_ragged,
            jit_fn=core._ragged_fn, raw_fn=core._ragged_fn.__wrapped__,
            donate_argnums=(1,),
            representatives=[
                dict(t_bucket=t_axis[-1], r_pad=r_axis[-1],
                     prefix_blocks=0),
            ],
            upcast_min_elems=min_elems,
        ))

    if cfg.unified_token_dispatch and cfg.prefill_token_budget > 0 and \
            getattr(model, "supports_unified_dispatch", False):
        bs = cfg.block_size
        # mirror engine _run_unified's flat-axis math exactly: a STATIC
        # decode region leads the axis, prefill spans pack the remainder
        d_region = -(-b // bs) * bs
        pf_budget = max(bs, cfg.prefill_token_budget - d_region)
        pf_budget = min(pf_budget, cfg.max_model_len - d_region)
        t_lo = cfg.bucket_for(d_region + bs)
        t_hi = cfg.bucket_for(d_region + pf_budget)
        tu_axis = [t for t in cfg.prefill_buckets if t_lo <= t <= t_hi]
        ru_axis = [r for r in _pow2s_upto(1 << max(0, (b - 1).bit_length()))
                   if r >= 2]  # a mixed dispatch has >= 2 rows

        def build_unified(t_bucket, r_pad, prefix_blocks):
            # pow2ceil(r_real) == r_pad needs more rows than the slots
            # can supply, or no block-wide span fits past the region
            min_rows = r_pad // 2 + 1 if r_pad > 1 else 1
            if min_rows > b or (t_bucket - d_region) // bs < 1 or not keyed(
                    "ragged", prefix_blocks, t_bucket):
                return None
            bufs, layout = flat_axis_operands(t_bucket, r_pad)
            return Signature(
                f"t={t_bucket},r={r_pad},pb={prefix_blocks}",
                (params, cache, keys, bufs),
                dict(layout=layout, row_tokens=d_region,
                     prefix_blocks=prefix_blocks, k_cand=K_MAX, exact=False),
            )

        eps.append(Entrypoint(
            name=f"engine.unified[{tag}]",
            axes={"t_bucket": tu_axis, "r_pad": ru_axis,
                  "prefix_blocks": pb_axis},
            build=build_unified,
            jit_fn=core._unified_fn, raw_fn=core._unified_fn.__wrapped__,
            donate_argnums=(1,),
            representatives=[
                dict(t_bucket=tu_axis[-1], r_pad=ru_axis[-1],
                     prefix_blocks=0),
            ],
            upcast_min_elems=min_elems,
        ))

    if cfg.spec_tokens > 0:
        # the sixth donated serving dispatch: the draft proposer's
        # ingest+draft step owns its own paged cache (engine/draft.py)
        from dynamo_tpu.engine.draft import DraftProposer

        proposer = DraftProposer(model, params, cfg)
        dcache = jax.eval_shape(
            lambda: model.init_kv_cache(
                cfg.num_blocks, cfg.block_size, cfg.cache_dtype)
        )

        def build_draft(u, m_used, k):
            args = (params, dcache,
                    _sds((b, u), i32), _sds((b, u), i32),
                    _sds((b, m_used), i32), _sds((b,), i32),
                    _sds((b, u), i32), _sds((b,), i32), _sds((b,), bool))
            return Signature(f"u={u},m={m_used},k={k}", args, dict(k=k))

        eps.append(Entrypoint(
            name=f"engine.draft_propose[{tag}]",
            axes={"u": _pow2s_upto(16), "m_used": _pow2s_upto(m),
                  "k": sorted({1, cfg.spec_tokens})},
            build=build_draft,
            jit_fn=proposer._fn, raw_fn=proposer._impl,
            donate_argnums=(1,),
            representatives=[dict(u=4, m_used=_pow2s_upto(m)[-1],
                                  k=cfg.spec_tokens)],
            upcast_min_elems=min_elems,
        ))
    return eps


def _llama_forward_entrypoint(tag: str, model_cfg, *, num_blocks: int,
                              block_size: int, batch: int,
                              max_model_len: int,
                              hbm_budget: Optional[int] = None,
                              cache_dtype=None) -> Entrypoint:
    """Model-level forward census (decode + prefill shapes) with an
    optional static HBM footprint check against a per-chip budget."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import LlamaModel

    model = LlamaModel(model_cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: model.init_kv_cache(
            num_blocks, block_size, cache_dtype or model_cfg.dtype)
    )
    m = -(-max_model_len // block_size)
    i32 = jnp.int32

    def build(phase):
        b, s = (batch, 1) if phase == "decode" else (1, max_model_len)
        statics = {} if phase == "decode" else dict(prefix_blocks=0)
        args = (params, _sds((b, s), i32), _sds((b, s), i32), cache,
                _sds((b, m), i32), _sds((b,), i32), _sds((b, s), i32))
        return Signature(phase, args, statics)

    def fwd(params, tokens, positions, cache, bt, lens, slots,
            prefix_blocks=None):
        return model.forward(params, tokens, positions, cache, bt, lens,
                             slots, prefix_blocks=prefix_blocks)

    hbm = None
    if hbm_budget is not None:
        def hbm():
            return _hbm_facts(build, fwd, params, cache, hbm_budget)

    return Entrypoint(
        name=f"models.llama.forward[{tag}]",
        axes={"phase": ["decode", "prefill"]},
        build=build,
        raw_fn=fwd,
        representatives=[dict(phase="decode")],
        upcast_min_elems=model_cfg.hidden_size,
        hbm=hbm,
    )


def _deepseek_forward_entrypoint() -> Entrypoint:
    """Tiny absorbed-MLA decode forward: census + dtype audit for the
    second model family (the latent-cache path has its own upcast and
    layout hazards — ROADMAP item 5 inherits this entry)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.deepseek import DeepseekConfig, DeepseekModel

    cfg = DeepseekConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=16, intermediate_size=64, moe_intermediate_size=32,
        n_routed_experts=4, num_experts_per_tok=2,
        first_k_dense_replace=1, dtype="bfloat16",
    )
    model = DeepseekModel(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_kv_cache(16, 8))
    m, b = 8, 2
    i32 = jnp.int32

    def build(phase):
        s = 1 if phase == "decode" else 16
        bb = b if phase == "decode" else 1
        args = (params, _sds((bb, s), i32), _sds((bb, s), i32), cache,
                _sds((bb, m), i32), _sds((bb,), i32), _sds((bb, s), i32))
        return Signature(phase, args, {})

    return Entrypoint(
        name="models.deepseek.forward[tiny-mla]",
        axes={"phase": ["decode", "prefill"]},
        build=build,
        raw_fn=model.forward,
        representatives=[dict(phase="decode")],
        upcast_min_elems=cfg.hidden_size,
    )


def _ops_entrypoints(model_cfg, engine_cfg) -> list[Entrypoint]:
    """The Pallas-backed serving ops, audited through the lowerings CPU
    produces (the XLA fallback paths — the manifest header records the
    caveat).  scatter_blocks_inplace is the fifth donated entrypoint."""
    import jax
    import jax.numpy as jnp

    import importlib

    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.ops import block_copy

    # ops/__init__ re-exports `paged_attention` (the function) under the
    # submodule's name — fetch the module itself
    pa = importlib.import_module("dynamo_tpu.ops.paged_attention")

    model = LlamaModel(model_cfg)
    cfg = engine_cfg
    cache = jax.eval_shape(
        lambda: model.init_kv_cache(cfg.num_blocks, cfg.block_size)
    )
    m = cfg.max_blocks_per_seq
    b = cfg.max_batch_size
    h, d = model_cfg.num_heads, model_cfg.head_dim
    hk = model_cfg.num_kv_heads
    dt = model_cfg.jax_dtype
    i32 = jnp.int32
    eps: list[Entrypoint] = []

    def build_decode(s):
        args = (_sds((b, s, h, d), dt), cache, _sds((), i32),
                _sds((b, m), i32), _sds((b,), i32), _sds((b, s), i32))
        return Signature(f"s={s}", args, {})

    eps.append(Entrypoint(
        name="ops.paged_attention_layer[tiny-llama]",
        axes={"s": [1, 3]},  # flash-decode and multi-query verify shapes
        build=build_decode,
        raw_fn=pa.paged_attention_layer,
        representatives=[dict(s=1)],
        upcast_min_elems=hk * d,
    ))

    def build_ragged_op(t, r):
        args = (_sds((1, t, h, d), dt), _sds((1, t, hk, d), dt),
                _sds((1, t, hk, d), dt), cache, _sds((), i32),
                _sds((r, m), i32), _sds((r,), i32), _sds((r,), i32),
                _sds((r,), i32), _sds((1, t), i32))
        return Signature(f"t={t},r={r}", args, dict(prefix_blocks=2))

    def ragged_op(q, k, v, cache, layer, bt, lens, starts, roff, ids,
                  prefix_blocks=0):
        return pa.ragged_prefill_attention(
            q, k, v, cache, layer, bt, lens, starts, roff, ids,
            prefix_blocks)

    eps.append(Entrypoint(
        name="ops.ragged_prefill_attention[tiny-llama]",
        axes={"t": [32, 64], "r": [2]},
        build=build_ragged_op,
        raw_fn=ragged_op,
        representatives=[dict(t=64, r=2)],
        upcast_min_elems=hk * d,
    ))

    def build_scatter(n):
        l_ = cache.shape[0]   # the cache's own layers (a looped model: T x L)
        blocks = _sds((l_, n, 2, cfg.block_size, hk * d), dt)
        args = (cache, _sds((n,), i32), blocks)
        return Signature(f"n={n}", args, {})

    eps.append(Entrypoint(
        name="ops.scatter_blocks_inplace[tiny-llama]",
        axes={"n": _pow2s_upto(8)},
        build=build_scatter,
        jit_fn=block_copy._scatter_donated,
        raw_fn=lambda cache, ids, blocks: jax.tree.map(
            lambda c, bl: c.at[:, ids].set(bl.astype(c.dtype)), cache,
            blocks),
        donate_argnums=(0,),
        representatives=[dict(n=4)],
    ))
    return eps


def build_registry() -> list[Entrypoint]:
    """The full compile-plane registry: every jitted serving entrypoint
    across a small representative config matrix.

    - ``tiny-llama``: bf16 tiny Llama under the test engine shape, all
      four EngineCore impls (spec + token-budget ragged prefill on).
    - ``tiny-llama-int8``: int8 quantized KV cache — the QuantKvCache
      pytree doubles the donated leaf count, so donation is audited per
      leaf.
    - ``tiny-mla``: absorbed-MLA DeepSeek decode forward.
    - ``llama3b-v5e``: representative single-chip deployment dims — the
      entry whose static HBM estimate gates config changes against the
      v5e budget.
    - ``ops.*``: the Pallas-backed ops via their XLA fallback lowerings.
    """
    from dynamo_tpu.models.config import ModelConfig

    tiny = _tiny_model_config()
    eps: list[Entrypoint] = []
    eps += _engine_entrypoints(
        "tiny-llama", tiny,
        _tiny_engine_config(spec_tokens=2, prefill_token_budget=64,
                            unified_token_dispatch=True),
    )
    eps += _engine_entrypoints(
        "tiny-llama-int8", tiny,
        # budget + unified on: the QuantKvCache pytree doubles the
        # donated leaf count of the ragged AND unified impls, so their
        # donation audit covers both cache layouts
        _tiny_engine_config(cache_dtype="int8", prefill_token_budget=64,
                            unified_token_dispatch=True),
    )
    eps.append(_llama_forward_entrypoint(
        "tiny-llama", tiny, num_blocks=64, block_size=8, batch=4,
        max_model_len=128,
    ))
    eps.append(_deepseek_forward_entrypoint())
    # Llama-3.2-3B-class dims on one v5e chip: ~6.4 GB bf16 params +
    # a 4096-block KV pool; a num_blocks/model_len bump that would OOM
    # the chip trips TR007 here before it ships.
    llama3b = ModelConfig(
        vocab_size=128256, hidden_size=3072, intermediate_size=8192,
        num_layers=28, num_heads=24, num_kv_heads=8, head_dim=128,
        max_position_embeddings=8192, dtype="bfloat16",
    )
    eps.append(_llama_forward_entrypoint(
        "llama3b-v5e", llama3b, num_blocks=4096, block_size=16, batch=16,
        max_model_len=8192,
        hbm_budget=int(V5E_HBM_BYTES * HBM_BUDGET_FRACTION),
    ))
    eps += _ops_entrypoints(
        tiny, _tiny_engine_config())
    return eps


# -------------------------------------------------------------- extraction ----


def _bytes_of(aval) -> int:
    try:
        return int(aval.size) * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


def _iter_subjaxprs(eqn):
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "jaxpr"):
                    yield x.jaxpr


def _walk_upcasts(jaxpr, min_elems: int, acc: dict[str, int]) -> dict:
    """Count widening convert_element_type sites (bf16/f16/int8 -> f32)
    with at least ``min_elems`` output elements, recursing into
    scan/pjit/cond sub-jaxprs.  Site key = src->dst dtype pair + output
    rank — stable across bucket sizes, so the manifest entry doesn't
    churn when a shape axis is re-bucketed."""
    for eqn in jaxpr.eqns:
        for sub in _iter_subjaxprs(eqn):
            _walk_upcasts(sub, min_elems, acc)
        if eqn.primitive.name != "convert_element_type":
            continue
        src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
        if str(src.dtype) not in ("bfloat16", "float16", "int8"):
            continue
        if str(dst.dtype) != "float32" or dst.size < min_elems:
            continue
        key = f"{src.dtype}->f32[r{len(dst.shape)}]"
        acc[key] = acc.get(key, 0) + 1
    return acc


def _peak_temp_bytes(jaxpr, skip_bytes: set) -> int:
    """Upper-bound single-eqn temporary footprint: max over eqns of the
    summed output bytes, recursing into sub-jaxprs.  Outputs whose byte
    size matches a donated input (``skip_bytes``) are excluded: those
    are the in-place cache update and its pure relayouts
    (reshape/transpose to per-head form), which XLA aliases rather than
    materializes under donation."""
    peak = 0
    for eqn in jaxpr.eqns:
        inner = [_peak_temp_bytes(s, skip_bytes) for s in
                 _iter_subjaxprs(eqn)]
        if inner:
            peak = max(peak, max(inner))
            continue
        size = sum(
            _bytes_of(v.aval) for v in eqn.outvars
            if _bytes_of(v.aval) not in skip_bytes
        )
        peak = max(peak, size)
    return peak


def _hbm_facts(build, fwd, params, cache, budget: int) -> dict:
    """Static per-chip footprint: params + KV pool + the larger of the
    decode/prefill peak temporaries (donated cache-shaped outputs are
    in-place and excluded)."""
    import jax

    params_bytes = sum(_bytes_of(l) for l in jax.tree.leaves(params))
    kv_bytes = sum(_bytes_of(l) for l in jax.tree.leaves(cache))
    skip = {_bytes_of(l) for l in jax.tree.leaves(cache)}
    peaks = {}
    for phase in ("decode", "prefill"):
        sig = build(phase)
        closed = jax.make_jaxpr(
            lambda *a: fwd(*a, **sig.statics))(*sig.args)
        peaks[phase] = _peak_temp_bytes(closed.jaxpr, skip)
    total = params_bytes + kv_bytes + peaks["decode"]
    return {
        "params_bytes": params_bytes,
        "kv_bytes": kv_bytes,
        "peak_temp_decode_bytes": peaks["decode"],
        # prefill peak is informational: the XLA fallback materializes
        # score matrices the Pallas kernels stream on-chip
        "peak_temp_prefill_bytes_xla": peaks["prefill"],
        "total_bytes": total,
        "budget_bytes": budget,
        "headroom_bytes": budget - total,
    }


def _closed_call(ep: Entrypoint, sig: Signature):
    fn = ep.raw_fn
    statics = dict(sig.statics)
    return lambda *a: fn(*a, **statics)


def _donation_facts(ep: Entrypoint) -> Optional[dict]:
    """Lower the representative signature and audit donation: every
    donated leaf must carry tf.aliasing_output in the module (TR004) and
    be read by the jaxpr (TR005)."""
    import jax

    if not ep.donate_argnums or ep.jit_fn is None:
        return None
    sig = ep.build(**ep.representatives[0])
    donated_leaves = sum(
        len(jax.tree.leaves(sig.args[i])) for i in ep.donate_argnums
    )
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = ep.jit_fn.lower(*sig.args, **sig.statics)
    text = lowered.as_text()
    aliased = len(re.findall(r"tf\.aliasing_output", text))
    notes = sorted({
        str(w.message).splitlines()[0][:160] for w in caught
        if "donat" in str(w.message).lower()
    })

    # dead-donation: donated invars with no reader in the jaxpr
    dead: list[str] = []
    if ep.raw_fn is not None:
        closed = jax.make_jaxpr(_closed_call(ep, sig))(*sig.args)
        offsets = []
        pos = 0
        for i, arg in enumerate(sig.args):
            n = len(jax.tree.leaves(arg))
            if i in ep.donate_argnums:
                offsets.extend(range(pos, pos + n))
            pos += n
        used: set = set()

        def mark(jaxpr):
            for eqn in jaxpr.eqns:
                used.update(id(v) for v in eqn.invars)
                for sub in _iter_subjaxprs(eqn):
                    mark(sub)
        mark(closed.jaxpr)
        used.update(id(v) for v in closed.jaxpr.outvars)
        for off in offsets:
            var = closed.jaxpr.invars[off]
            if id(var) not in used:
                dead.append(f"leaf{off}")
    return {
        "donated_leaves": donated_leaves,
        "aliased_leaves": aliased,
        "dead_leaves": dead,
        "lowering_notes": notes,
        "signature": sig.label,
    }


def collect_facts(registry: Optional[list[Entrypoint]] = None) -> dict:
    """Extract the full fact snapshot for every registered entrypoint.
    Pure shape-level work: eval_shape / make_jaxpr / lower over
    ShapeDtypeStructs — no weights, no compiles, no model math."""
    import jax

    registry = registry if registry is not None else build_registry()
    facts: dict[str, dict] = {}
    for ep in registry:
        signatures = enumerate_signatures(ep)
        # stability probe: a second enumeration must produce the same
        # keys (an id-keyed static would hash differently per build)
        stable = _matrix_hash(enumerate_signatures(ep)) == \
            _matrix_hash(signatures)
        traced: dict[str, str] = {}
        for rep in ep.representatives:
            sig = ep.build(**rep)
            if sig is None:
                continue
            target = (ep.jit_fn if ep.raw_fn is None else
                      _closed_call(ep, sig))
            out = jax.eval_shape(target, *sig.args)
            leaves = jax.tree.leaves(out)
            traced[sig.label] = (
                f"{len(leaves)} outputs, "
                f"{sum(_bytes_of(l) for l in leaves)} bytes"
            )
        upcasts: dict[str, int] = {}
        if ep.upcast_min_elems and ep.raw_fn is not None:
            sig = ep.build(**ep.representatives[0])
            closed = jax.make_jaxpr(_closed_call(ep, sig))(*sig.args)
            _walk_upcasts(closed.jaxpr, ep.upcast_min_elems, upcasts)
        facts[ep.name] = {
            "axes": {k: list(v) for k, v in sorted(ep.axes.items())},
            "n_signatures": len(signatures),
            "signature_hash": _matrix_hash(signatures),
            "stable": stable,
            "traced": traced,
            "donation": _donation_facts(ep),
            "upcasts": dict(sorted(upcasts.items())),
            "hbm": ep.hbm() if ep.hbm is not None else None,
        }
    return facts


# ------------------------------------------------------------------- check ----


def check_facts(facts: dict, manifest: Manifest) -> list[TraceFinding]:
    """Findings = drift (facts vs manifest snapshot) + intrinsic
    compile-plane defects.  Intrinsic findings (TR004-TR007) can be
    accepted with a justification; drift (TR001-TR003) is resolved by
    fixing the code or re-snapshotting with ``--update``."""
    findings: list[TraceFinding] = []
    known = manifest.entrypoints
    for name in sorted(set(facts) - set(known)):
        findings.append(TraceFinding(
            name, "TR001", "added",
            "entrypoint not in the committed manifest — audit it and "
            "re-snapshot (`dynamo-tpu lint --trace --update-baseline`)",
        ))
    for name in sorted(set(known) - set(facts)):
        findings.append(TraceFinding(
            name, "TR001", "removed",
            "manifest entrypoint no longer registered — re-snapshot if "
            "the removal is intended",
        ))
    for name, f in sorted(facts.items()):
        committed = known.get(name)
        if committed is not None:
            if f["signature_hash"] != committed.get("signature_hash"):
                old_axes, new_axes = committed.get("axes"), f["axes"]
                detail = (
                    f"axes {old_axes} -> {new_axes}"
                    if old_axes != new_axes else
                    f"{committed.get('n_signatures')} -> "
                    f"{f['n_signatures']} signatures (same axes: an arg "
                    "shape/dtype or static changed)"
                )
                findings.append(TraceFinding(
                    name, "TR002", "matrix",
                    "declared trace-signature matrix drifted from the "
                    f"manifest: {detail} — a retrace surface changed; "
                    "verify bucketing, then re-snapshot",
                ))
        # TR006 is intrinsic: every upcast site class fires with its
        # count embedded in the acceptance key, so a count CHANGE (a new
        # f32 site on a reduced-precision hot path) invalidates the
        # accepted entry and trips the gate until re-justified
        for ul, count in f["upcasts"].items():
            old = (committed or {}).get("upcasts", {}).get(ul)
            drift = f" (manifest had {old})" if old not in (None, count) \
                else ""
            findings.append(TraceFinding(
                name, "TR006", f"{ul}x{count}",
                f"{count} widening-conversion site(s) {ul} on a "
                f"reduced-precision hot path{drift} — accept with a "
                "justification only if the accumulation is by design",
            ))
        if not f["stable"]:
            findings.append(TraceFinding(
                name, "TR003", "unstable",
                "signature matrix hashes differently across two "
                "enumerations: a dispatch static is unhashed/id-keyed "
                "(e.g. a config object) — every call would retrace",
            ))
        don = f.get("donation")
        if don is not None:
            if don["aliased_leaves"] < don["donated_leaves"]:
                findings.append(TraceFinding(
                    name, "TR004",
                    f"unaliased={don['donated_leaves'] - don['aliased_leaves']}",
                    f"{don['donated_leaves'] - don['aliased_leaves']} of "
                    f"{don['donated_leaves']} donated leaves carry no "
                    "tf.aliasing_output in the lowered module "
                    f"(sig {don['signature']}): the donated buffer is "
                    "copied, not updated in place — the lowered-HLO "
                    "complement of AST rule DT103",
                ))
            for leaf in don["dead_leaves"]:
                findings.append(TraceFinding(
                    name, "TR005", leaf,
                    f"donated {leaf} is never read by the jaxpr — dead "
                    "donation: drop it from donate_argnums or wire the "
                    "buffer through",
                ))
        hbm = f.get("hbm")
        if hbm is not None and hbm["total_bytes"] > hbm["budget_bytes"]:
            findings.append(TraceFinding(
                name, "TR007", "total",
                f"static footprint {hbm['total_bytes']:,} B (params "
                f"{hbm['params_bytes']:,} + KV {hbm['kv_bytes']:,} + "
                f"decode peak {hbm['peak_temp_decode_bytes']:,}) exceeds "
                f"the per-chip budget {hbm['budget_bytes']:,} B",
            ))
    return sorted(findings)


# --------------------------------------------------------------------- CLI ----


def run_trace(args, out) -> int:
    """`dynamo-tpu lint --trace`: text or stable JSON, exit 1 on any
    non-accepted finding, `--update-baseline` re-snapshots the manifest
    (carrying justifications by key)."""
    manifest_path = Path(
        getattr(args, "manifest", None) or DEFAULT_MANIFEST_PATH
    )
    manifest = Manifest.load(manifest_path)
    facts = collect_facts()
    findings = check_facts(facts, manifest)

    if getattr(args, "update_baseline", False):
        # drift findings (TR001-TR003) are resolved by the snapshot
        # itself; intrinsic findings become accepted entries
        intrinsic = [f for f in findings
                     if f.rule in ("TR004", "TR005", "TR006", "TR007")]
        Manifest.from_facts(facts, intrinsic, manifest).save(manifest_path)
        print(
            f"trace manifest updated: {len(facts)} entrypoints, "
            f"{len(intrinsic)} accepted finding"
            f"{'' if len(intrinsic) == 1 else 's'} -> {manifest_path}",
            file=out,
        )
        return 0

    fresh = manifest.filter(findings)
    n_accepted = len(findings) - len(fresh)
    if getattr(args, "fmt", "text") == "json":
        doc = {
            "findings": [f.to_json() for f in fresh],
            "accepted": n_accepted,
            "total": len(findings),
            "entrypoints": sorted(facts),
        }
        print(json.dumps(doc, indent=2, sort_keys=True), file=out)
    else:
        for f in fresh:
            print(f.render(), file=out)
        print(
            f"{len(fresh)} trace finding{'s' if len(fresh) != 1 else ''} "
            f"({n_accepted} accepted) over {len(facts)} entrypoints",
            file=out,
        )
    return 1 if fresh else 0
