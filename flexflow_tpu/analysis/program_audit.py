"""fflint layer 2: the traced/compiled-program audit.

The AST rules (``lint.py``) see code; this layer sees the PROGRAMS the
runtime actually builds, on the same 8-device virtual CPU mesh the
test suite uses, and verifies the properties prose alone used to carry
(CLAUDE.md "Design invariants"; the PR-5 cross-mesh numerics hazards
were exactly bugs a pass over the traced programs would have flagged):

- **FFP000 coverage** — every op class registered in
  ``flexflow_tpu.ops`` must appear in the audit catalog, so adding an
  op without audit coverage fails the audit instead of silently
  narrowing it.
- **FFP001 AD-reachability** — an op's training ``forward`` jaxpr may
  contain no ``pallas_call`` primitive outside a ``custom_vjp`` wrap
  unless the op declares ``sparse_keys`` (the sparse-protocol escape
  hatch) or the program is a forward-only serving program.  This is
  the CLAUDE.md reachability invariant as a checked property.
- **FFP002 purity** — no host-effect primitive (``*_callback``,
  infeed/outfeed) in any compiled train/serve program: a host callback
  inside the fused step reintroduces the per-step host round-trip the
  whole dispatch architecture exists to remove.
- **FFP003 donation** — buffers declared donated in
  ``build_superstep`` / ``build_compiled_step`` /
  ``build_decode_superstep`` (and the plain train step) are actually
  aliased in the lowered computation (``input_output_alias``), so the
  in-place update guarantees (sparse tables, KV caches, k-step carry)
  hold at the XLA level, not just in the jit signature.
- **FFP004 dispatch/fence accounting** — the statically derived
  programs-per-step of every executor family equals the telemetry
  formulas the PR-6 cost model prices: ``2*S*ceil(m/c)`` host-driven,
  ``1`` compiled, ``1/k`` fused superstep (stacked metrics really
  carry k steps per dispatch).
- **FFH001 collectives** — the relocated post-SPMD HLO audit
  (``analysis/hlo.py``): no all-gather materializes a full sharded
  activation in the compiled step.

``audit_repo(fast=True)`` is the trace-only layer (< 60 s on the
1-CPU box: ``jax.make_jaxpr``/``eval_shape``, zero compiles);
``fast=False`` adds the compile-level checks (donation, FFH001, and a
real host-driven + compiled pipeline step cross-checked against the
live telemetry counters).  ``audit_executor`` / ``audit_serving``
run the trace-only checks over ONE already-built executor — the
``--dry-run`` hook: every app dry run audits the exact programs that
run would build.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple


def ensure_cpu_mesh() -> None:
    """Force the 8-device virtual CPU mesh (tests/conftest.py rules)
    BEFORE jax initializes a backend — the audit traces on the CPU
    mesh and must never take the chip from the run it audits."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    # Also where the environment names an accelerator platform.
    jax.config.update("jax_platforms", "cpu")


@dataclasses.dataclass
class ProgramViolation:
    rule: str
    program: str    # e.g. "full_mesh/train_step", "serving/decode_k8"
    message: str
    op: str = ""    # owning model op when attributable

    def __str__(self) -> str:
        where = f"{self.program}" + (f" [{self.op}]" if self.op else "")
        return f"{where}: {self.rule} {self.message}"


# -- jaxpr walking -----------------------------------------------------------

#: Primitives whose bodies carry their own AD rules — a pallas_call
#: inside one is differentiable by construction and sanctioned.
_CUSTOM_AD_PRIMS = frozenset({
    "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call", "custom_jvp_call_jaxpr",
})

#: Host-effect primitive names (FFP002); ``jax.debug.print`` lowers to
#: its own ``debug_print`` primitive, every other host hop to a
#: ``*callback``.
_HOST_EFFECT_MARKERS = ("callback", "debug_print", "infeed", "outfeed")


def _sub_jaxprs(params: Dict[str, Any]):
    import jax.extend.core as jcore

    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def iter_eqns(jaxpr, *, descend_custom_ad: bool = False):
    """Yield every eqn recursively.  By default the bodies of
    custom-AD primitives are NOT descended into (their contents are
    differentiable by the wrap)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if not descend_custom_ad and eqn.primitive.name in _CUSTOM_AD_PRIMS:
            continue
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, descend_custom_ad=descend_custom_ad)


def _eqn_scope(eqn) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except Exception:
        return ""


def _attribute_op(scope: str, op_names: Sequence[str]) -> str:
    """Owning model op of an eqn: the last op-name component in the
    jax named-scope path (``Executor.forward`` wraps each op in
    ``jax.named_scope(op.name)``)."""
    components = re.split(r"[/()]", scope)
    best, best_pos = "", -1
    for name in op_names:
        for i, comp in enumerate(components):
            if comp == name and i > best_pos:
                best, best_pos = name, i
    return best


def ad_reachability_violations(
    closed_jaxpr,
    program: str,
    op_names: Sequence[str] = (),
    sparse_ok: Sequence[str] = (),
    serving: bool = False,
) -> List[ProgramViolation]:
    """FFP001 over one traced program: ``pallas_call`` primitives not
    wrapped in custom-AD, attributed to their op via the named-scope
    stack; ops declaring ``sparse_keys`` are exempt (the sparse
    protocol differentiates w.r.t. gathered rows, never through the
    kernel), as are forward-only serving programs."""
    if serving:
        return []
    out = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        op = _attribute_op(_eqn_scope(eqn), op_names)
        if op and op in sparse_ok:
            continue
        out.append(ProgramViolation(
            "FFP001", program,
            "pallas_call without a custom_vjp wrap on the training "
            "path (CLAUDE.md: AD-rule-less kernels are reachable only "
            "via the sparse protocol or serving programs)",
            op=op,
        ))
    return out


def purity_violations(closed_jaxpr, program: str) -> List[ProgramViolation]:
    """FFP002 over one traced program."""
    out = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr, descend_custom_ad=True):
        name = eqn.primitive.name
        if any(m in name for m in _HOST_EFFECT_MARKERS):
            out.append(ProgramViolation(
                "FFP002", program,
                f"host-effect primitive {name!r} in a compiled "
                f"program: reintroduces the per-dispatch host "
                f"round-trip",
                op=_attribute_op(_eqn_scope(eqn), ()),
            ))
    return out


# -- donation ---------------------------------------------------------------

def _alias_count(compiled_text: str) -> int:
    """Number of aliased parameters in compiled HLO text
    (``input_output_alias={ {0}: (0, {}, may-alias), ... }``)."""
    m = re.search(r"input_output_alias=\{", compiled_text)
    if m is None:
        return 0
    i, depth = m.end(), 1
    while i < len(compiled_text) and depth:
        depth += {"{": 1, "}": -1}.get(compiled_text[i], 0)
        i += 1
    block = compiled_text[m.end():i]
    return len(re.findall(r":\s*\(\s*\d+\s*,", block))


def donation_violations(
    jitted, program: str, donated_avals: Sequence[Any], *args
) -> List[ProgramViolation]:
    """FFP003: compile ``jitted`` at ``*args`` avals and check every
    leaf of the declared-donated trees is actually aliased in the
    lowered computation."""
    import jax

    expected = len([
        x for x in jax.tree.leaves(list(donated_avals)) if x is not None
    ])
    try:
        txt = jitted.lower(*args).compile().as_text()
    except Exception as e:  # surface, never crash the audit
        return [ProgramViolation(
            "FFP003", program, f"could not compile for donation audit: "
            f"{type(e).__name__}: {e}")]
    actual = _alias_count(txt)
    if actual < expected:
        return [ProgramViolation(
            "FFP003", program,
            f"{actual} of {expected} declared-donated buffers are "
            f"aliased in the lowered computation — donation silently "
            f"dropped (in-place update guarantee broken)")]
    return []


# -- the audit catalog -------------------------------------------------------

def _tiny_config(**kw):
    from flexflow_tpu.config import FFConfig

    cfg = FFConfig(**kw)
    cfg.num_devices = 8
    return cfg


def _conv_graph():
    """Conv2D, Pool2D, BatchNorm, Flat, Linear, SoftmaxCrossEntropy."""
    import jax.numpy as jnp

    from flexflow_tpu.graph import FFModel

    ff = FFModel(_tiny_config(batch_size=8))
    img = ff.create_tensor((8, 16, 16, 3), name="image")
    lbl = ff.create_tensor((8,), dtype=jnp.int32, name="label")
    t = ff.conv2d(img, 8, 3, 3, 1, 1, 1, 1, activation="relu", name="conv1")
    t = ff.batch_norm(t, relu=True, name="bn1")
    t = ff.pool2d(t, 2, 2, 2, 2, 0, 0, name="pool1")
    t = ff.flat(t, name="flat")
    t = ff.dense(t, 16, activation="relu", name="fc1")
    t = ff.dense(t, 10, name="fc2")
    ff.softmax(t, lbl, name="softmax")
    return ff


def _dlrm_graph():
    """Embedding, MultiEmbedding, HeteroEmbedding, Concat,
    DotInteraction, Reshape, Linear, MSELoss."""
    import jax.numpy as jnp

    from flexflow_tpu.graph import FFModel

    ff = FFModel(_tiny_config(batch_size=8))
    dense_in = ff.create_tensor((8, 4), name="dense_input")
    ids1 = ff.create_tensor((8, 1), dtype=jnp.int32, name="ids1")
    ids2 = ff.create_tensor((8, 2), dtype=jnp.int32, name="ids2")
    ids3 = ff.create_tensor((8, 2), dtype=jnp.int32, name="ids3")
    lbl = ff.create_tensor((8, 1), name="label")
    x = ff.dense(dense_in, 4, activation="relu", name="bot0")
    e1 = ff.embedding(ids1, 16, 4, name="emb1")
    e1 = ff.reshape(e1, (8, 1, 4), name="rs1")
    e2 = ff.multi_embedding(ids2, 2, 16, 4, name="emb2")
    e3 = ff.hetero_embedding(ids3, (8, 12), 4, name="emb3")
    sparse = ff.concat([e1, e2, e3], axis=1, name="cat")
    z = ff.dot_interaction(x, sparse, name="interact")
    z = ff.dense(z, 1, activation="sigmoid", name="top0")
    ff.mse_loss(z, lbl, name="mse")
    return ff


def _transformer_graph():
    """WordEmbedding, PositionEmbedding, MultiHeadAttention, LayerNorm,
    Add, MixtureOfExperts, Linear, SoftmaxCrossEntropy."""
    from flexflow_tpu.models.transformer import build_transformer_lm

    return build_transformer_lm(
        batch_size=8, seq_len=8, vocab_size=64, d_model=16, num_heads=2,
        num_layers=1, d_ff=32, moe_experts=2, config=_tiny_config(
            batch_size=8
        ),
    )


def _latent_moe_graph():
    """RMSNorm, LatentAttention, Multiply, the sorted MixtureOfExperts."""
    from flexflow_tpu.models.transformer import DEEPSEEK_V3_TINY, build_lm

    return build_lm({**DEEPSEEK_V3_TINY, "num_hidden_layers": 2}, 8, 8,
                    _tiny_config(batch_size=8))


def _delta_graph():
    """KimiDeltaAttention beside grouped-query MultiHeadAttention (one
    period's first two layers of the Solar-Open2 family)."""
    from flexflow_tpu.models.transformer import SOLAR_OPEN2_TINY, build_lm

    return build_lm({**SOLAR_OPEN2_TINY, "num_hidden_layers": 2}, 8, 8,
                    _tiny_config(batch_size=8))


def _stream_graph():
    """HyperConnectionPre and HyperConnectionPost round a dense and an
    expert layer of the Xing4.0 family (a compressed query, YaRN)."""
    from flexflow_tpu.models.transformer import XING4_TINY, build_lm

    return build_lm({**XING4_TINY, "num_hidden_layers": 2}, 8, 8,
                    _tiny_config(batch_size=8))


def _short_conv_graph():
    """GatedShortConv beside grouped-query MultiHeadAttention under a
    head tied to the token table (the LFM2-MoE family's first three
    layers: two dense feed-forwards and an expert layer)."""
    from flexflow_tpu.models.transformer import LFM2_TINY, build_lm

    return build_lm({**LFM2_TINY, "num_hidden_layers": 3}, 8, 8,
                    _tiny_config(batch_size=8))


def _serving_graph():
    """The graph ServingExecutor is audited on (no MoE: serving drives
    the plain transformer LM, apps/serve.py)."""
    from flexflow_tpu.models.transformer import build_transformer_lm

    return build_transformer_lm(
        batch_size=8, seq_len=16, vocab_size=64, d_model=16, num_heads=2,
        num_layers=1, d_ff=32, config=_tiny_config(batch_size=8),
    )


def _rnn_graph():
    """LSTM, WordEmbedding, Dropout, Linear, SoftmaxCrossEntropy."""
    from flexflow_tpu.models.nmt import build_nmt

    return build_nmt(
        batch_size=8, src_len=6, tgt_len=6, vocab_size=32, embed_dim=8,
        hidden_size=8, num_layers=2, dropout=0.2,
        config=_tiny_config(batch_size=8),
    )


def _pipeline_graph():
    """A 4-Linear stack split into 2 stages — the host-driven AND
    compiled pipeline family (Linear-only stages keep the compiled
    path eligible, ``compiled_unsupported_reason``)."""
    import jax.numpy as jnp

    from flexflow_tpu.graph import FFModel
    from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore

    ff = FFModel(_tiny_config(batch_size=16))
    x = ff.create_tensor((16, 8), name="x")
    lbl = ff.create_tensor((16,), dtype=jnp.int32, name="label")
    t = ff.dense(x, 16, activation="relu", name="l0")
    t = ff.dense(t, 16, activation="relu", name="l1")
    t = ff.dense(t, 16, activation="relu", name="l2")
    t = ff.dense(t, 8, name="l3")
    ff.softmax(t, lbl, name="softmax")
    store = StrategyStore(8)
    store.set("l0", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
    store.set("l1", ParallelConfig(n=4, device_ids=(0, 1, 2, 3)))
    store.set("l2", ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
    store.set("l3", ParallelConfig(n=4, device_ids=(4, 5, 6, 7)))
    return ff, store


def catalog_models():
    """(name, FFModel) audit catalog — together these must cover every
    registered op class (FFP000)."""
    return [
        ("conv", _conv_graph()),
        ("dlrm", _dlrm_graph()),
        ("transformer_moe", _transformer_graph()),
        ("nmt", _rnn_graph()),
        ("deepseek_v3", _latent_moe_graph()),
        ("solar_open2", _delta_graph()),
        ("xing4_0", _stream_graph()),
        ("lfm2_moe", _short_conv_graph()),
    ]


def coverage_violations(models) -> List[ProgramViolation]:
    """FFP000: every Op subclass exported from ``flexflow_tpu.ops``
    appears in the catalog."""
    import flexflow_tpu.ops as ops_pkg
    from flexflow_tpu.ops.base import Op

    registered = {
        name for name in ops_pkg.__all__
        if isinstance(getattr(ops_pkg, name), type)
        and issubclass(getattr(ops_pkg, name), Op)
        and getattr(ops_pkg, name) is not Op
    }
    covered: Set[str] = set()
    for _, ff in models:
        for op in ff.layers:
            covered.add(type(op).__name__)
    missing = sorted(registered - covered)
    return [
        ProgramViolation(
            "FFP000", "catalog",
            f"registered op {name!r} is not covered by the audit "
            f"catalog — add it to a catalog graph so its training "
            f"forward stays audited",
        )
        for name in missing
    ]


# -- per-executor audits -----------------------------------------------------

def _sparse_exempt_ops(model) -> List[str]:
    return [op.name for op in model.layers if op.sparse_keys()]


def audit_executor(ex, program_prefix: str = "") -> List[ProgramViolation]:
    """Trace-only audit of ONE built executor (full-mesh ``Executor``
    or ``PipelineExecutor``) — the ``--dry-run`` hook.  AD-reachability
    + purity over the real traced programs, plus the static dispatch
    accounting for the pipeline families."""
    from flexflow_tpu.runtime.pipeline import PipelineExecutor

    if isinstance(ex, PipelineExecutor):
        return _audit_pipeline(ex, program_prefix, fast=True)
    return _audit_full_mesh(ex, program_prefix, fast=True)


def _audit_full_mesh(ex, prefix: str = "", fast: bool = True):
    import jax

    name = (prefix or "full_mesh") + "/train_step"
    out: List[ProgramViolation] = []
    op_names = [op.name for op in ex.model.layers]
    sparse_ok = _sparse_exempt_ops(ex.model)
    params, opt_state, state = ex._abstract_init()
    batch = ex._abstract_batch()

    # Forward-only jaxpr: FFP001 attribution happens here (the
    # train-step jaxpr holds the already-transposed program).
    def fwd(p, s, b):
        loss, metrics, new_state, _ = ex.forward(p, s, b, training=True)
        return loss, metrics, new_state

    try:
        fwd_jaxpr = jax.make_jaxpr(fwd)(params, state, batch)
    except Exception as e:
        return out + [ProgramViolation(
            "FFP001", name,
            f"training forward failed to trace: {type(e).__name__}: {e}")]
    out += ad_reachability_violations(
        fwd_jaxpr, name, op_names, sparse_ok
    )

    # The whole train step (grad + optimizer): purity, and — because
    # value_and_grad must trace through every op — the AD property
    # holds end to end or this trace raises.
    try:
        step_jaxpr = jax.make_jaxpr(ex.build_train_step())(
            params, opt_state, state, batch
        )
    except Exception as e:
        return out + [ProgramViolation(
            "FFP001", name,
            f"train step failed to trace (autodiff through the op "
            f"graph): {type(e).__name__}: {e}")]
    out += purity_violations(step_jaxpr, name)

    # FFP004, fused-superstep accounting: k steps really ride ONE
    # dispatch — the stacked metrics carry a leading k.
    if ex.strategy.superstep_capable():
        k = 3
        stacked = {
            n: jax.ShapeDtypeStruct((k,) + tuple(a.shape), a.dtype)
            for n, a in batch.items()
        }
        try:
            _, _, _, ms = jax.eval_shape(
                ex.build_superstep(k), params, opt_state, state, stacked
            )
            bad = [
                key for key, v in ms.items() if v.shape[:1] != (k,)
            ]
            if bad:
                out.append(ProgramViolation(
                    "FFP004", (prefix or "full_mesh") + f"/superstep_k{k}",
                    f"superstep metrics {bad} do not carry the (k,) "
                    f"leading dim — the 1/k programs-per-step "
                    f"accounting would be wrong"))
        except Exception as e:
            out.append(ProgramViolation(
                "FFP004", (prefix or "full_mesh") + f"/superstep_k{k}",
                f"build_superstep failed to trace: "
                f"{type(e).__name__}: {e}"))

    if not fast:
        out += donation_violations(
            ex.train_step, name, (params, opt_state, state),
            params, opt_state, state, batch,
        )
        if ex.strategy.superstep_capable():
            k = 3
            stacked = {
                n: jax.ShapeDtypeStruct((k,) + tuple(a.shape), a.dtype)
                for n, a in batch.items()
            }
            out += donation_violations(
                ex.build_superstep(k),
                (prefix or "full_mesh") + f"/superstep_k{k}",
                (params, opt_state, state),
                params, opt_state, state, stacked,
            )
        out += _hlo_collective_violations(ex, name)
    return out


def _hlo_collective_violations(ex, program: str) -> List[ProgramViolation]:
    """FFH001 (the relocated runtime/audit.py check) folded into the
    one audit surface."""
    from flexflow_tpu.analysis import hlo

    try:
        hlo_text = ex.lower_train_step().compile().as_text()
        bad = hlo.full_activation_allgathers(ex, hlo_text)
        bad_tables = hlo.full_table_allgathers(ex, hlo_text)
    except Exception as e:
        return [ProgramViolation(
            "FFH001", program,
            f"could not run the HLO collective audit: "
            f"{type(e).__name__}: {e}")]
    return [
        ProgramViolation(
            "FFH001", program,
            f"all-gather materializes a full sharded activation "
            f"({c.shape}, {c.elements} elements/device) — the "
            f"replicate-then-slice pattern decomposed resharding "
            f"exists to prevent",
            op=c.op_name,
        )
        for c in bad
    ] + [
        ProgramViolation(
            "FFH002", program,
            f"all-gather materializes a full row-sharded embedding "
            f"table ({c.shape}, {c.elements} elements/device) — "
            f"--shard-embeddings exists so no device holds the whole "
            f"table; the gather must stay shard-local + psum",
            op=c.op_name,
        )
        for c in bad_tables
    ]


def _pipeline_stage_avals(pipe):
    """Thread abstract microbatch shapes through the stages (the
    ``hlo.pipeline_collective_bytes`` walk, trace-only)."""
    import jax
    import jax.numpy as jnp

    graph_inputs = {t.name for t in pipe.model.input_tensors}
    boundary: Dict[str, Any] = {}
    m = pipe.microbatches
    dloss = jax.ShapeDtypeStruct((), jnp.float32)
    per_stage = []
    for si, st in enumerate(pipe.stages):
        ex = pipe.stage_ex[si]
        p, o, s = ex._abstract_init()
        inputs = {}
        for n in st.in_names:
            spec = pipe._spec_of[n]
            if n in graph_inputs:
                shape = (spec.shape[0] // m,) + tuple(spec.shape[1:])
                inputs[n] = jax.ShapeDtypeStruct(shape, spec.dtype)
            else:
                inputs[n] = boundary[n]
        outs = jax.eval_shape(pipe._fwd_fns[si], p, s, inputs)[0]
        boundary.update(outs)
        douts = {n: boundary[n] for n in st.out_names}
        per_stage.append((p, o, s, inputs, douts, dloss))
    return per_stage


def _audit_pipeline(pipe, prefix: str = "", fast: bool = True):
    import jax

    out: List[ProgramViolation] = []
    prefix = prefix or ("pipeline_compiled" if pipe.compiled
                        else "pipeline_host")
    S = len(pipe.stages)
    m, c = pipe.microbatches, pipe.chunk
    op_names = [op.name for op in pipe.model.layers]
    sparse_ok = _sparse_exempt_ops(pipe.model)
    per_stage = _pipeline_stage_avals(pipe)

    if pipe.compiled:
        params = {si: ps[0] for si, ps in enumerate(per_stage)}
        opt_state = {si: ps[1] for si, ps in enumerate(per_stage)}
        state = {si: ps[2] for si, ps in enumerate(per_stage)}
        batch = {
            t.name: jax.ShapeDtypeStruct(t.shape, t.dtype)
            for t in pipe.model.input_tensors
        }
        name = f"{prefix}/compiled_step"
        try:
            jaxpr = jax.make_jaxpr(pipe._compiled_step_impl)(
                params, opt_state, state, batch
            )
        except Exception as e:
            return out + [ProgramViolation(
                "FFP001", name,
                f"compiled step failed to trace: {type(e).__name__}: {e}")]
        out += ad_reachability_violations(jaxpr, name, op_names, sparse_ok)
        out += purity_violations(jaxpr, name)
        # FFP004: the compiled step is ONE program (and k of them
        # fuse to 1/k) — the cost-model formula must agree.
        formula = _exec_config_programs_per_step(S, m, c, True)
        if formula != 1.0:
            out.append(ProgramViolation(
                "FFP004", name,
                f"cost model prices the compiled pipeline step at "
                f"{formula} programs/step; the executor builds 1"))
        k = 3
        if _exec_config_programs_per_step(S, m, c, True, k) != 1.0 / k:
            out.append(ProgramViolation(
                "FFP004", name,
                "cost model does not price the fused pipeline "
                "superstep at 1/k programs/step"))
        if not fast:
            out += donation_violations(
                pipe.build_compiled_step(), name,
                (params, opt_state, state),
                params, opt_state, state, batch,
            )
    else:
        for si in range(S):
            p, o, s, inputs, douts, dloss = per_stage[si]
            for kind, fn, args in (
                ("fwd", pipe._fwd_fns[si], (p, s, inputs)),
                ("bwd", pipe._bwd_fns[si], (p, s, inputs, douts, dloss)),
            ):
                name = f"{prefix}/stage{si}_{kind}"
                try:
                    jaxpr = jax.make_jaxpr(fn)(*args)
                except Exception as e:
                    out.append(ProgramViolation(
                        "FFP001", name,
                        f"stage program failed to trace: "
                        f"{type(e).__name__}: {e}"))
                    continue
                out += ad_reachability_violations(
                    jaxpr, name, op_names, sparse_ok
                )
                out += purity_violations(jaxpr, name)
        # FFP004 static: schedule length == 2*S*ceil(m/c) == the
        # cost-model formula.
        n_units = math.ceil(m / c)
        sched = len(pipe.build_schedule(S, n_units))
        expect = 2 * S * n_units
        formula = _exec_config_programs_per_step(S, m, c, False)
        if not (sched == expect == formula):
            out.append(ProgramViolation(
                "FFP004", f"{prefix}/schedule",
                f"programs/step disagree: schedule={sched}, "
                f"2*S*ceil(m/c)={expect}, cost-model formula={formula}"))
    return out


def _exec_config_programs_per_step(stages, microbatches, chunk,
                                   compiled, steps_per_call=1):
    """The PR-6 cost-model accounting, via its own implementation."""
    from flexflow_tpu.search.execution import ExecutionConfig
    from flexflow_tpu.parallel.strategy import StrategyStore

    return ExecutionConfig(
        store=StrategyStore.data_parallel(8), stages=stages,
        microbatches=microbatches, chunk=chunk, compiled=compiled,
        steps_per_call=steps_per_call,
    ).programs_per_step()


def _serving_cache_avals(sex):
    """Cache avals in the executor's OWN layout: padded per-slot rows
    or the paged block pool (SERVING.md "Cache layout")."""
    import jax

    return sex._cache_tree(
        sex._cache_specs,
        lambda ce: jax.ShapeDtypeStruct(
            sex._cache_shape(ce, sex.paged)[0], ce.dtype))


def _serving_decode_args(sex, params, op_state, caches):
    """The decode-superstep argument avals for the executor's layout:
    the paged variant carries the per-slot block table between caches
    and positions."""
    import jax
    import jax.numpy as jnp

    B = sex.max_batch
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    args = (params, op_state, caches)
    if sex.paged:
        args += (jax.ShapeDtypeStruct((B, sex.blocks_per_slot),
                                      jnp.int32),)
    return args + (pos, tok)


def audit_serving(sex, decode_steps: int = 8, prefix: str = "serving",
                  sample=None, speculate: int = 0) -> List[ProgramViolation]:
    """Trace-only audit of a built ``ServingExecutor``: purity of
    every prefill bucket and the fused decode superstep (FFP001 is
    exempt — forward-only programs may reach AD-rule-less kernels),
    plus the K-tokens-per-dispatch shape of the decode accounting.
    Covers whichever cache layout / mesh shard / sampling mode the
    executor was built with — the paged variant traces with the block
    table, the sharded one through its shard_map-wrapped kernels, and
    ``sample=(temperature, top_k, seed)`` audits the in-program
    sampling head.  ``speculate=d`` additionally audits the spec
    family: every draft-prefill bucket and the fused draft+verify
    round, whose FFP004 accounting is (d+1) tokens per dispatch."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import clamp_fused_steps

    decode_steps = clamp_fused_steps(decode_steps, what="decode_steps")
    out: List[ProgramViolation] = []
    params, _opt, op_state = Executor(
        sex.model, config=sex.config
    )._abstract_init()
    B = sex.max_batch
    for bucket in sex.buckets:
        toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        ln = jax.ShapeDtypeStruct((), jnp.int32)
        name = f"{prefix}/prefill_L{bucket}"
        try:
            jaxpr = jax.make_jaxpr(sex.build_prefill(bucket))(
                params, op_state, toks, ln
            )
        except Exception as e:
            out.append(ProgramViolation(
                "FFP002", name,
                f"prefill failed to trace: {type(e).__name__}: {e}"))
            continue
        out += purity_violations(jaxpr, name)
    caches = _serving_cache_avals(sex)
    if getattr(sex, "prefix_cache", False):
        # Prefix sharing (SERVING.md "Prefix sharing"): the offset
        # prefill reads shared pool blocks and computes only the tail.
        o = sex.kv_block
        ids = jax.ShapeDtypeStruct((1,), jnp.int32)
        for bucket in sex.buckets:
            if bucket <= o:
                continue
            toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
            ln = jax.ShapeDtypeStruct((), jnp.int32)
            name = f"{prefix}/prefill_from_L{bucket}_o{o}"
            try:
                jaxpr = jax.make_jaxpr(sex.build_prefill_from(bucket, o))(
                    params, op_state, caches, ids, toks, ln
                )
            except Exception as e:
                out.append(ProgramViolation(
                    "FFP002", name,
                    f"offset prefill failed to trace: "
                    f"{type(e).__name__}: {e}"))
                continue
            out += purity_violations(jaxpr, name)
    k = decode_steps
    name = f"{prefix}/decode_k{k}"
    decode = sex.build_decode_superstep(k, sample=sample)
    args = _serving_decode_args(sex, params, op_state, caches)
    if sample is not None:
        args += (jax.ShapeDtypeStruct((B,), jnp.int32),)
    try:
        jaxpr = jax.make_jaxpr(decode)(*args)
    except Exception as e:
        return out + [ProgramViolation(
            "FFP002", name,
            f"decode superstep failed to trace: {type(e).__name__}: {e}")]
    out += purity_violations(jaxpr, name)
    # FFP004: K tokens per dispatch across the whole slot batch.
    shapes = jax.eval_shape(decode, *args)
    toks_out = shapes[3][0]
    if tuple(toks_out.shape) != (k, B):
        out.append(ProgramViolation(
            "FFP004", name,
            f"decode superstep stacks {tuple(toks_out.shape)} tokens, "
            f"expected (k={k}, B={B}) — one fence per K tokens would "
            f"be false"))
    if speculate:
        out += _audit_spec(sex, speculate, prefix, sample,
                           params, op_state, caches)
    return out


def _audit_spec(sex, d: int, prefix: str, sample,
                params, op_state, caches) -> List[ProgramViolation]:
    """The speculative program family (SERVING.md "Speculative
    decoding"): purity of every draft-prefill bucket and the fused
    draft+verify round, plus its FFP004 accounting — the one fence
    reads back a (d+1, B) verified-token stack (up to d+1 tokens per
    dispatch across the whole slot batch)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.runtime.trainer import clamp_fused_steps

    d = clamp_fused_steps(d, what="speculate")
    out: List[ProgramViolation] = []
    B, S = sex.max_batch, sex.max_seq
    for bucket in sex.buckets:
        toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        name = f"{prefix}/draft_prefill_L{bucket}"
        try:
            jaxpr = jax.make_jaxpr(sex.build_draft_prefill(bucket))(
                params, op_state, toks
            )
        except Exception as e:
            out.append(ProgramViolation(
                "FFP002", name,
                f"draft prefill failed to trace: "
                f"{type(e).__name__}: {e}"))
            continue
        out += purity_violations(jaxpr, name)
    # The draft model's own caches are ALWAYS the padded layout
    # (init_draft_cache), whatever the verify caches use.
    dcaches = sex._cache_tree(
        sex._draft_cache_specs,
        lambda ce: jax.ShapeDtypeStruct(
            sex._cache_shape(ce, False)[0], ce.dtype))
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    args = (params, params, op_state, caches, dcaches)
    if sex.paged:
        args += (jax.ShapeDtypeStruct((B, sex.blocks_per_slot),
                                      jnp.int32),)
    args += (pos, tok)
    if sample is not None:
        args += (jax.ShapeDtypeStruct((B,), jnp.int32),)
    name = f"{prefix}/spec_d{d}"
    spec = sex.build_spec_step(d, sample=sample)
    try:
        jaxpr = jax.make_jaxpr(spec)(*args)
    except Exception as e:
        return out + [ProgramViolation(
            "FFP002", name,
            f"spec round failed to trace: {type(e).__name__}: {e}")]
    out += purity_violations(jaxpr, name)
    # FFP004: the single fence carries a (d+1, B) verified-token
    # stack — up to d+1 accepted tokens per dispatch.
    shapes = jax.eval_shape(spec, *args)
    ys = shapes[4][0]
    if tuple(ys.shape) != (d + 1, B):
        out.append(ProgramViolation(
            "FFP004", name,
            f"spec round stacks {tuple(ys.shape)} verified tokens, "
            f"expected (d+1={d + 1}, B={B}) — the tokens-per-dispatch "
            f"accounting would be false"))
    return out


def _donation_serving(sex, decode_steps: int = 8) -> List[ProgramViolation]:
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.trainer import clamp_fused_steps

    decode_steps = clamp_fused_steps(decode_steps, what="decode_steps")
    params, _opt, op_state = Executor(
        sex.model, config=sex.config
    )._abstract_init()
    caches = _serving_cache_avals(sex)
    args = _serving_decode_args(sex, params, op_state, caches)
    # Donated decode state = caches + pos + tok; the block table (the
    # paged variant's extra arg) is host-owned and NOT donated.
    donated = (caches, args[-2], args[-1])
    return donation_violations(
        sex.build_decode_superstep(decode_steps),
        f"serving/decode_k{decode_steps}", donated, *args,
    )


# -- dispatch-accounting cross-check against LIVE telemetry ------------------

def _accounting_live_violations() -> List[ProgramViolation]:
    """Full mode only: run one REAL host-driven and one compiled
    pipeline step on the virtual mesh under an in-memory Telemetry and
    assert the counters land exactly on the formulas."""
    import numpy as np

    from flexflow_tpu.runtime import telemetry as _telemetry
    from flexflow_tpu.runtime.pipeline import PipelineExecutor

    out: List[ProgramViolation] = []
    for compiled, chunk in ((False, 2), (True, 1)):
        ff, store = _pipeline_graph()
        pipe = PipelineExecutor(ff, store, microbatches=4, chunk=chunk,
                                compiled=compiled)
        S, m, c = len(pipe.stages), pipe.microbatches, pipe.chunk
        expect = 1 if compiled else 2 * S * math.ceil(m / c)
        formula = _exec_config_programs_per_step(S, m, c, compiled)
        params, opt_state, state = pipe.init(seed=0)
        rng = np.random.default_rng(0)
        batch = {
            "x": rng.standard_normal((16, 8)).astype(np.float32),
            "label": rng.integers(0, 8, size=(16,)).astype(np.int32),
        }
        with _telemetry.Telemetry(directory=None) as tel:
            pipe.train_step(params, opt_state, state, pipe.shard_batch(batch))
            got = tel.counts["host_programs"]
        name = ("pipeline_compiled" if compiled else "pipeline_host") \
            + "/live_step"
        if not (got == len(pipe.last_schedule) == expect == formula):
            out.append(ProgramViolation(
                "FFP004", name,
                f"live programs/step disagree: telemetry={got}, "
                f"last_schedule={len(pipe.last_schedule)}, "
                f"2*S*ceil(m/c) or 1={expect}, cost model={formula}"))
    return out


# -- the whole-repo audit ----------------------------------------------------

def audit_repo(fast: bool = True) -> List[ProgramViolation]:
    """Audit every registered op and every executor family (full-mesh,
    pipeline host-driven, pipeline compiled, serving) on the 8-dev
    virtual mesh.  ``fast`` = trace-only (no compiles)."""
    ensure_cpu_mesh()

    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.pipeline import PipelineExecutor
    from flexflow_tpu.runtime.serving import ServingExecutor

    models = catalog_models()
    out: List[ProgramViolation] = list(coverage_violations(models))

    # Full-mesh family: every catalog model under the DP strategy.
    for name, ff in models:
        ex = Executor(ff)
        out += _audit_full_mesh(ex, prefix=f"full_mesh/{name}", fast=fast)

    # Pipeline families (host-driven c in {1, 2}, compiled).
    ff, store = _pipeline_graph()
    for chunk in (1, 2):
        pipe = PipelineExecutor(ff, store, microbatches=4, chunk=chunk)
        out += _audit_pipeline(
            pipe, prefix=f"pipeline_host_c{chunk}", fast=fast
        )
    ffc, storec = _pipeline_graph()
    pipec = PipelineExecutor(ffc, storec, microbatches=4, compiled=True)
    out += _audit_pipeline(pipec, prefix="pipeline_compiled", fast=fast)

    # Serving families: padded baseline, in-program sampling head,
    # paged KV pool, the sharded (n x c) decode mesh, the speculative
    # draft+verify round (full-graph self-draft: draft_layers is a
    # deployment knob, the program shape is the audited property), and
    # the paged x sharded composition.
    sex = ServingExecutor(_serving_graph(), max_batch=2, max_seq=16,
                          buckets=(8, 16))
    out += audit_serving(sex, decode_steps=4)
    out += audit_serving(sex, decode_steps=4, prefix="serving_sampled",
                         sample=(0.8, 8, 0))
    sex_paged = ServingExecutor(_serving_graph(), max_batch=2, max_seq=16,
                                buckets=(8, 16), kv_block=4)
    out += audit_serving(sex_paged, decode_steps=4, prefix="serving_paged")
    sex_shard = ServingExecutor(_serving_graph(), max_batch=2, max_seq=16,
                                buckets=(8, 16), shard=(2, 2))
    out += audit_serving(sex_shard, decode_steps=4, prefix="serving_sharded")
    out += audit_serving(sex, decode_steps=4, prefix="serving_spec",
                         speculate=4)
    sex_ps = ServingExecutor(_serving_graph(), max_batch=2, max_seq=16,
                             buckets=(8, 16), kv_block=4, shard=(2, 2))
    out += audit_serving(sex_ps, decode_steps=4,
                         prefix="serving_paged_sharded")
    # Prefix-sharing family (SERVING.md "Prefix sharing"): the paged
    # pool with the content-hash index armed — audits the offset
    # prefill (build_prefill_from) alongside the usual programs.
    sex_pfx = ServingExecutor(_serving_graph(), max_batch=2, max_seq=16,
                              buckets=(8, 16), kv_block=4,
                              prefix_cache=True)
    out += audit_serving(sex_pfx, decode_steps=4, prefix="serving_prefix")
    # Fleet family (SERVING.md "Fleet"): routing and redistribution are
    # pure host arithmetic — a fleet adds NO new program shapes, it
    # replicates the single-replica family.  Audit a second
    # independently-built replica executor to pin exactly that.
    sex_fleet = ServingExecutor(_serving_graph(), max_batch=2, max_seq=16,
                                buckets=(8, 16))
    out += audit_serving(sex_fleet, decode_steps=4, prefix="serving_fleet")

    if not fast:
        out += _donation_serving(sex, decode_steps=4)
        out += _donation_serving(sex_paged, decode_steps=4)
        out += _accounting_live_violations()
    return out


def format_report(violations: Sequence[ProgramViolation]) -> str:
    if not violations:
        return "program audit: clean"
    lines = [str(v) for v in violations]
    lines.append(f"program audit: {len(violations)} violation(s)")
    return "\n".join(lines)


def summary_line(violations: Sequence[ProgramViolation]) -> str:
    """The one-line ``--dry-run`` verdict."""
    if not violations:
        return "audit: clean"
    rules = sorted({v.rule for v in violations})
    return (f"audit: {len(violations)} violation(s) "
            f"[{', '.join(rules)}] — run python -m flexflow_tpu.analysis")
