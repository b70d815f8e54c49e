"""Mixture-of-Experts FFN with expert parallelism.

The reference's expert parallelism is per-table placement: each DLRM
embedding table is its own op pinned to one GPU by the strategy
(``dlrm_strategy.cc:5-36``), with Legion coherence moving each table's
inputs to its device.  This op is that idea generalized to transformer
scale — many expert FFNs, tokens routed to experts — expressed the
TPU-native way (the GShard/Switch formulation): routing becomes dense
one-hot dispatch/combine einsums, the expert dimension carries the
``c`` sharding tag, and GSPMD inserts the token all-to-alls between
the sample-sharded activations and the expert-sharded FFN batch —
exactly where Legion inserted the per-table copies.

Design notes (TPU-first):
- Top-1 (switch) routing with a static per-expert capacity
  ``ceil(cf * S / E)``: every shape is static, so the whole layer is
  three einsums + a gate matmul on the MXU — no dynamic shapes, no
  scatter.  Tokens overflowing an expert's capacity pass through with
  a zero expert contribution (the standard switch-transformer drop).
- Routing math runs in f32 (gate logits, cumulative positions) for
  stable argmax/cumsum under bf16 activations.
- The auxiliary load-balance loss (mean expert load x mean gate prob
  x E) is returned as op state-free METRIC ``{name}_aux_loss`` via the
  loss-op protocol of the consumer; here it is exposed as an output
  metric hook: `aux_loss_weight` > 0 adds it into the training loss
  through ``is_loss`` accounting.

A second formulation inside the same op, ``dispatch="sorted"`` (what
the DeepSeek-V3 family's expert layers need): no capacity, no dropped
token, no ``(S, E, C)`` one-hot tensors.  The (token, choice)
assignments are sorted by expert, padded so that every tile of rows
belongs to one expert, and one grouped matrix product runs over the
experts that received any (``pallas_kernels.grouped_matmul`` when
serving, ``lax.ragged_dot`` otherwise: differentiable, and the oracle).
With it come the routers beyond softmax-top-k (``router="sigmoid"``
scores, a selection bias that chooses but does not weigh, normalised and
scaled weights), gated expert MLPs, shared experts every token passes
through, and ``held_experts``: the experts THIS chip holds.  The router
keeps its full width and its experts per token; the op computes its own
experts' part of the result (plus the shared experts, which every chip
computes alike) and leaves the rest out.  A router without an auxiliary
loss makes the op an ordinary one (``is_loss`` false), which is what
lets the serving executor keep it.

How the rows are sized.  An op that holds every expert gives each of a
segment's ``A = tokens x top_k`` assignments a row (``A`` plus the tile
padding of the experts), and so does a decode step, whose rows are
tile padding and not assignments.  A prefill-sized segment of an op
that holds a share has rows for ``held_rows_bound(A)`` assignments:
those a uniform router would put on its experts, with
``HELD_ROWS_MARGIN`` to spare.  The gather in front of the two
products, the products' results, the gather behind them and the f32 sum
of a token's choices (a sorted scatter-add over the held assignments in
token order) are that much smaller.  How many fall here is data: the
sorted held assignments are walked in windows of that many, one window
unless the router sends more here, so any routing is computed exactly
and nothing is dropped; the serving counter ``held_rows_overflow`` says
how often a segment took more than one.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from flexflow_tpu.initializers import GlorotUniform, ZeroInitializer
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.activations import apply_activation, check_activation
from flexflow_tpu.ops.base import Op, ParamSpec, TensorSpec


class MixtureOfExperts(Op):
    """Switch-style MoE FFN over (batch, seq, d_model).

    Strategy axes: ``n`` shards tokens (batch), ``c`` shards the
    EXPERT dimension of every expert parameter and the expert compute
    batch — the per-op placement freedom the reference used to pin
    DLRM tables, realized as GSPMD all-to-alls instead of coherence
    copies.  ``is_loss`` contributes the weighted aux balance loss so
    routing stays trained (metrics report it separately).
    """

    is_loss = True
    #: MoE is the heaviest op in its block and its loss term is a cheap
    #: scalar byproduct — per-layer remat must include it despite
    #: ``is_loss`` (the executor's guard exists for terminal loss ops).
    allow_remat = True

    def __init__(
        self,
        name: str,
        x: TensorSpec,
        num_experts: int,
        ffn_dim: int,
        capacity_factor: float = 1.25,
        activation: str = "gelu",
        aux_loss_weight: float = 1e-2,
        top_k: int = 1,
        kernel_initializer=None,
        dispatch: str = "capacity",
        router: str = "softmax",
        gated: bool = False,
        shared_experts: int = 0,
        held_experts: Optional[Sequence[int]] = None,
        selection_bias: bool = False,
        norm_topk_prob: bool = True,
        routed_scale: float = 1.0,
        n_group: int = 1,
        topk_group: int = 1,
        norm_topk_eps: float = 1e-20,
    ):
        super().__init__(name, [x])
        if dispatch not in ("capacity", "sorted"):
            raise ValueError(f"moe dispatch {dispatch!r}: capacity or sorted")
        if router not in ("softmax", "sigmoid"):
            raise ValueError(f"moe router {router!r}: softmax or sigmoid")
        if dispatch == "capacity" and (
                router != "softmax" or gated or shared_experts
                or held_experts is not None or selection_bias
                or routed_scale != 1.0 or n_group != 1):
            raise ValueError(
                f"moe {name}: sigmoid routing, gated or shared experts, a "
                f"selection bias, expert groups and held_experts need "
                f"dispatch='sorted'")
        if n_group < 1 or num_experts % n_group or \
                not 1 <= topk_group <= n_group or \
                (n_group > 1 and (num_experts // n_group < 2
                                  or top_k > topk_group * (num_experts // n_group))):
            raise ValueError(
                f"moe {name}: n_group={n_group!r}, topk_group={topk_group!r}: "
                f"groups of at least two experts that divide {num_experts}, "
                f"and the kept ones hold the {top_k} a token chooses")
        held = tuple(range(num_experts)) if held_experts is None else \
            tuple(sorted(int(e) for e in held_experts))
        if not held or len(set(held)) != len(held) or \
                held[0] < 0 or held[-1] >= num_experts:
            raise ValueError(
                f"moe {name}: held_experts {held_experts!r} must be distinct "
                f"ids in [0, {num_experts})")
        #: The sorted formulation has no auxiliary loss: an ordinary op.
        self.is_loss = dispatch == "capacity"
        if dispatch == "sorted":
            self.serving_stats = ("experts_touched", "expert_load_max")
            if len(held) < num_experts:
                self.serving_stats += ("held_rows_overflow",)
        self.held = held
        assert x.ndim == 3, f"moe input must be (batch, seq, d), got {x.shape}"
        check_activation(activation)
        b, t, d = x.shape
        tokens = b * t
        assert num_experts >= 2, "moe needs >= 2 experts"
        assert 1 <= top_k <= num_experts, (
            f"top_k={top_k} must be in [1, num_experts={num_experts}]"
        )
        self.attrs = dict(
            num_experts=num_experts,
            ffn_dim=ffn_dim,
            capacity_factor=capacity_factor,
            # Declared-shape capacity (introspection; forward recomputes
            # from the runtime token count so microbatched execution —
            # accum scan, pipeline microbatches — drops tokens at the
            # same per-token rate as the full batch).
            capacity=self.capacity_for(
                tokens * top_k, capacity_factor, num_experts
            ),
            activation=activation,
            aux_loss_weight=aux_loss_weight,
            # k routed experts per token (1 = switch; 2 = GShard top-2
            # with gates renormalized over the chosen k).  Static
            # shapes: k one-hot dispatch slots, no dynamic scatter.
            top_k=top_k,
            dispatch=dispatch,
            router=router,
            gated=gated,
            shared_experts=int(shared_experts),
            selection_bias=bool(selection_bias),
            norm_topk_prob=bool(norm_topk_prob),
            routed_scale=float(routed_scale),
            n_group=int(n_group),
            topk_group=int(topk_group),
            # What ``norm_topk_prob`` adds to the sum it divides by (a
            # family's own: DeepSeek-V3's 1e-20, LFM2's 1e-6).
            norm_topk_eps=float(norm_topk_eps),
        )
        self.d_model = d
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        self._make_output(x.shape, x.dtype, x.dim_axes)

    @staticmethod
    def capacity_for(tokens: int, cf: float, e: int) -> int:
        """Static per-expert slot count for ``tokens`` routed tokens,
        padded to a lane-friendly multiple of 8."""
        cap = int(-(-cf * tokens // e))
        return max(8, -(-cap // 8) * 8)

    def capacity(self, tokens: int) -> int:
        """Per-expert slots for ``tokens`` routed tokens; top-k routing
        places k assignments per token, so demand (and capacity) scale
        by k — the GShard sizing convention."""
        return self.capacity_for(
            tokens * self.attrs.get("top_k", 1),
            self.attrs["capacity_factor"], self.attrs["num_experts"],
        )

    def param_specs(self) -> Dict[str, ParamSpec]:
        d = self.d_model
        e = self.attrs["num_experts"]
        f = self.attrs["ffn_dim"]
        dt = self.outputs[0].dtype
        ki = self.kernel_initializer
        if self.attrs["dispatch"] == "sorted":
            return self._sorted_param_specs(d, e, f, dt, ki)
        return {
            # Router stays replicated (tiny).
            "gate": ParamSpec((d, e), dt, ki),
            # Expert weights: expert dim carries the 'c' tag -> a
            # c-degree strategy shards experts across the mesh (the
            # reference's one-table-per-GPU, ``dlrm_strategy.cc:11-19``).
            "w1": ParamSpec((e, d, f), dt, ki, ("c", None, None)),
            "b1": ParamSpec((e, f), dt, ZeroInitializer(), ("c", None)),
            "w2": ParamSpec((e, f, d), dt, ki, ("c", None, None)),
            "b2": ParamSpec((e, d), dt, ZeroInitializer(), ("c", None)),
        }

    def forward(self, params, xs, state, training):
        (x,) = xs
        if self.attrs["dispatch"] == "sorted":
            return self._forward_sorted(params, x, state)
        b, t, d = x.shape
        e = self.attrs["num_experts"]
        s = b * t
        # Capacity follows the RUNTIME token count (microbatched
        # executions shrink the sample dim; per-token drop behavior
        # must match the declared-batch step).
        cap = self.capacity(s)
        xf = x.reshape(s, d)

        # -- routing (f32) --------------------------------------------
        k = self.attrs.get("top_k", 1)
        logits = (xf.astype(jnp.float32) @ params["gate"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                  # (S, E)
        topk_p, topk_e = jax.lax.top_k(probs, k)                 # (S, K)
        if k == 1:
            gates = topk_p                                       # raw prob
        else:
            # GShard convention: renormalize over the chosen k so the
            # combine weights sum to 1 per token.
            gates = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)
        # Slot-major queueing: ALL first choices claim capacity before
        # any second choice (GShard's priority rule), each slot in
        # token order; a token past capacity loses that slot only.
        counts = jnp.zeros((e,), jnp.float32)  # slots consumed so far
        dispatch = jnp.zeros((s, e, cap), jnp.float32)           # (S, E, C)
        combine = jnp.zeros((s, e, cap), jnp.float32)
        keep_total = jnp.float32(0.0)
        first_mask = None
        for j in range(k):
            mask = jax.nn.one_hot(topk_e[:, j], e, dtype=jnp.float32)
            if j == 0:
                first_mask = mask
            pos = ((jnp.cumsum(mask, axis=0) - 1.0) + counts[None, :]) * mask
            pos_tok = jnp.sum(pos, axis=-1).astype(jnp.int32)    # (S,)
            keep = (pos_tok < cap).astype(jnp.float32)
            d_j = (
                mask[:, :, None]
                * keep[:, None, None]
                * jax.nn.one_hot(pos_tok, cap, dtype=jnp.float32)[:, None, :]
            )
            dispatch = dispatch + d_j
            combine = combine + d_j * gates[:, j][:, None, None]
            keep_total = keep_total + jnp.sum(keep)
            # Overflowed tokens still consume their queue slot (cumsum
            # semantics, same as the k=1 path).
            counts = counts + jnp.sum(mask, axis=0)

        # -- expert compute (MXU; all-to-all inserted by GSPMD) -------
        cd = x.dtype
        expert_in = jnp.einsum("sec,sd->ecd", dispatch.astype(cd), xf)
        h = jnp.einsum("ecd,edf->ecf", expert_in, params["w1"])
        h = apply_activation(h + params["b1"][:, None, :],
                             self.attrs["activation"])
        y_e = jnp.einsum("ecf,efd->ecd", h, params["w2"])
        y_e = y_e + params["b2"][:, None, :]
        y = jnp.einsum("sec,ecd->sd", combine.astype(cd), y_e)

        # -- aux load-balance loss (Switch eq. 4; first-choice load,
        # which reduces to the k=1 formula when k == 1) ---------------
        load = jnp.mean(first_mask, axis=0)                      # (E,)
        importance = jnp.mean(probs, axis=0)                     # (E,)
        aux = e * jnp.sum(load * importance)
        w = self.attrs["aux_loss_weight"]
        loss = (w * aux).astype(jnp.float32) if training else jnp.float32(0.0)
        metrics = {
            f"{self.name}_aux_loss": aux.astype(jnp.float32),
            # Dropped ASSIGNMENTS (a top-2 token losing one slot counts
            # once; it still flows through its surviving slot).
            f"{self.name}_dropped": jnp.float32(s * k) - keep_total,
        }
        return (loss, metrics, [y.reshape(b, t, d)]), state

    # -- the sorted (dropless) formulation -----------------------------------

    serving_aware = True

    def _sorted_param_specs(self, d, e, f, dt, ki) -> Dict[str, ParamSpec]:
        a = self.attrs
        eh = len(self.held)
        tag = ("c", None, None)
        # The router is held and run in f32, whatever the model's dtype.
        specs = {"gate": ParamSpec((d, e), jnp.float32, ki)}
        if a["selection_bias"]:
            specs["e_bias"] = ParamSpec((e,), jnp.float32, ZeroInitializer())
        if a["gated"]:
            specs["w_gate"] = ParamSpec((eh, d, f), dt, ki, tag)
            specs["w_up"] = ParamSpec((eh, d, f), dt, ki, tag)
            specs["w_down"] = ParamSpec((eh, f, d), dt, ki, tag)
        else:
            specs["w1"] = ParamSpec((eh, d, f), dt, ki, tag)
            specs["w2"] = ParamSpec((eh, f, d), dt, ki, tag)
        fs = a["shared_experts"] * f
        if fs:
            if a["gated"]:
                specs["s_gate"] = ParamSpec((d, fs), dt, ki, (None, "c"))
            specs["s_up"] = ParamSpec((d, fs), dt, ki, (None, "c"))
            specs["s_down"] = ParamSpec((fs, d), dt, ki, ("c", None))
        return specs

    def route(self, params, xf):
        """``(idx (T, k) int32, w (T, k) f32)``: the experts a token
        chooses, over the router's full width, and the weights of
        their outputs.  All in f32, the product at full precision (a
        bf16 product flips choices between near-equal scores).  Under
        ``n_group`` groups (DeepSeek-V3's ``noaux_tc``) a group stands by
        the sum of its two largest ``score + bias``, the ``topk_group``
        best groups stay (the lower index among equals) and the top-k
        is taken among their experts alone."""
        a = self.attrs
        logits = jnp.dot(xf.astype(jnp.float32), params["gate"],
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if a["router"] == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        choice = scores + params["e_bias"] if a["selection_bias"] else scores
        if a["n_group"] > 1:
            with jax.named_scope("ff_route_group"):
                choice = self._kept_groups(choice)
        _, idx = jax.lax.top_k(choice, a["top_k"])
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if a["top_k"] > 1 and a["norm_topk_prob"]:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + a["norm_topk_eps"])
        return idx, w * a["routed_scale"]

    def _kept_groups(self, choice):
        """``choice`` (T, e) with the experts of every group but the
        ``topk_group`` best at ``-inf``."""
        g, keep = self.attrs["n_group"], self.attrs["topk_group"]
        by_group = choice.reshape(choice.shape[0], g, -1)
        standing = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)      # (T, g)
        _, best = jax.lax.top_k(standing, keep)                         # (T, keep)
        kept = jnp.any(best[:, :, None] == jnp.arange(g)[None, None, :], axis=1)
        return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(choice.shape)

    def _mlp(self, x, p, names, product, fused_gate=None):
        """One (gated) MLP over the leaves ``p[names]`` (``(in, out)``,
        or gated ``(gate, up, down)``) given how to multiply:
        ``product(x, w)``, and where a kernel has it ``fused_gate(x,
        w_gate, w_up) = silu(x w_gate) * (x w_up)``."""
        act = self.attrs["activation"]
        if not self.attrs["gated"]:
            w_in, w_out = (p[n] for n in names)
            return product(apply_activation(product(x, w_in), act), w_out)
        w_gate, w_up, w_down = (p[n] for n in names)
        if fused_gate is not None and act == "silu":
            return product(fused_gate(x, w_gate, w_up), w_down)
        return product(apply_activation(product(x, w_gate), act)
                       * product(x, w_up), w_down)

    #: Assignments' outputs one pass of the sorted formulation may hold
    #: in f32 (``tokens x top_k x d``): a longer forward walks its
    #: tokens in equal segments under this, one after another (experts
    #: see a token alone, so segments change nothing but the order).
    SEGMENT_BYTES = 1 << 29

    #: Room a prefill-sized segment's rows leave over the assignments a
    #: uniform router would put on the held experts (``A * held /
    #: routed``); a segment that puts more there walks them in more
    #: than one window of rows, and ``held_rows_overflow`` counts it.
    HELD_ROWS_MARGIN = 1.5

    def held_rows_bound(self, assignments: int) -> Optional[int]:
        """How many of a segment's ``assignments`` its rows are sized
        for where that is fewer than all: the held share with its
        margin, in a prefill-sized segment (whole 128-row tiles an
        expert; a decode step's rows are tile padding, not assignments:
        the rule ``grouped_tile_rows`` draws).  None where every
        assignment gets a row.  Expert groups leave the share where it
        is: a uniform router keeps a held expert's group ``topk_group /
        n_group`` of the time and then puts ``n_group / topk_group``
        times its uniform share of a token's choices there, ``top_k x
        held / routed`` either way (8 x 16 / 256 = 0.5 a token at 16
        held of 256 in 8 groups of which 4 stay).  What grows is a
        token's spread, a whole group in or out (variance 0.66 a token
        against 0.46 there): 37 assignments over a segment of 2048
        tokens, against the margin's 512."""
        eh = len(self.held)
        bound = math.ceil(self.HELD_ROWS_MARGIN * assignments * eh
                          / self.attrs["num_experts"])
        if bound < assignments and \
                pallas_kernels.grouped_tile_rows(assignments, eh) == 128:
            return bound
        return None

    def _forward_sorted(self, params, x, state):
        b, t, d = x.shape
        T = b * t
        serving = bool(state.get("serving"))
        per_token = self.attrs["top_k"] * d * 4
        n = -(-T * per_token // self.SEGMENT_BYTES)
        while T % n:
            n += 1
        if n == 1:
            y, counts = self._sorted_tokens(params, x.reshape(T, d), serving)
            by_segment = counts
        else:
            y, by_segment = jax.lax.map(
                lambda xs: self._sorted_tokens(params, xs, serving),
                x.reshape(n, T // n, d))
            counts = jnp.sum(by_segment, axis=0)
        out = [y.reshape(b, t, d)]
        if not serving:
            return out, state
        # Routing counters, fetched at the fence the step already has.
        eh = len(self.held)
        new_state = dict(state)
        new_state["stats"] = {
            "experts_touched": jnp.sum(counts > 0).astype(jnp.float32),
            "expert_load_max": jnp.max(counts).astype(jnp.float32)
            * eh / jnp.maximum(jnp.sum(counts), 1).astype(jnp.float32),
        }
        bound = self.held_rows_bound(T // n * self.attrs["top_k"])
        if bound is not None:
            # The share of the segments that put more on the held
            # experts than one window of the held-sized rows takes.
            new_state["stats"]["held_rows_overflow"] = jnp.mean(
                jnp.sum(by_segment.reshape(n, eh), axis=1) > bound,
                dtype=jnp.float32)
        return out, new_state

    def _sorted_tokens(self, params, xf, serving: bool):
        """``xf`` (T, d) -> ``(y (T, d), assignments a held expert)``."""
        a = self.attrs
        T, d = xf.shape
        k, e, eh = a["top_k"], a["num_experts"], len(self.held)
        A = T * k
        idx, w = self.route(params, xf)
        # Global expert id -> row of this chip's expert arrays, or eh
        # for an expert held elsewhere (its assignments sort last and
        # are left out).
        local_of = np.full((e,), eh, np.int32)
        local_of[list(self.held)] = np.arange(eh, dtype=np.int32)
        local = jnp.asarray(local_of)[idx].reshape(A)
        here = local < eh
        counts = jnp.sum(local[:, None] == jnp.arange(eh)[None, :], axis=0,
                         dtype=jnp.int32)                        # (eh,)
        tm = pallas_kernels.grouped_tile_rows(A, eh)
        bound = self.held_rows_bound(A)
        if bound is None:
            y = self._routed_terms(params, xf, w, local, here, counts, tm,
                                   serving)
        else:
            y = self._held_terms(params, xf, w, local, counts, tm, serving,
                                 bound)
        if a["shared_experts"]:
            shared = ("s_gate", "s_up", "s_down") if a["gated"] else \
                ("s_up", "s_down")
            y = y + self._mlp(xf, params, shared,
                              lambda x, w: x @ w).astype(jnp.float32)
        return y.astype(xf.dtype), counts

    def _routed_terms(self, params, xf, w, local, here, counts, tm, serving):
        """The held experts' weighted outputs summed a token, ``(T, d)``
        f32, with a row for every one of the ``A`` assignments."""
        a = self.attrs
        T, d = xf.shape
        k, eh = a["top_k"], len(self.held)
        A = T * k
        rows = -(-(A + min(eh, A) * (tm - 1)) // tm) * tm
        padded = -(-counts // tm) * tm
        p_end = jnp.cumsum(padded)
        start = jnp.cumsum(counts) - counts
        # Stable sort by expert: position p of the sorted order, whose
        # expert is key[p], goes to row p_start[key] + (p - start[key]).
        key, tok, slot = jax.lax.sort(
            (local, jnp.repeat(jnp.arange(T, dtype=jnp.int32), k),
             jnp.arange(A, dtype=jnp.int32)), num_keys=1)
        kc = jnp.minimum(key, eh - 1)
        dest = jnp.where(key < eh,
                         (p_end - padded)[kc] + jnp.arange(A) - start[kc],
                         rows)
        src_tok = jnp.zeros((rows,), jnp.int32).at[dest].set(tok, mode="drop")
        row_of = jnp.zeros((A,), jnp.int32).at[slot].set(
            jnp.minimum(dest, rows - 1))
        ys = self._expert_rows(params, xf[src_tok], padded, p_end, tm, serving)
        # Back to (token, choice) order; an assignment held elsewhere
        # reads some row and is masked (not multiplied: the row may be
        # one the kernel never wrote).
        y_tk = ys[row_of].reshape(T, k, d).astype(jnp.float32)
        return jnp.sum(
            jnp.where(here.reshape(T, k, 1), y_tk * w[..., None], 0.0), axis=1)

    def _held_terms(self, params, xf, w, local, counts, tm, serving,
                    bound: int):
        """The same sum through rows for ``bound`` assignments at a
        time: the assignments sorted by expert (the held ones first)
        are walked in windows of ``bound``, as many as hold a held one
        (one, unless the router sends more here than the bound), each
        through its own rows, and a token's terms are added up by a
        ``segment_sum``-like scatter in token order."""
        a = self.attrs
        T, d = xf.shape
        k, eh = a["top_k"], len(self.held)
        A = T * k
        rows = -(-(bound + min(eh, bound) * (tm - 1)) // tm) * tm
        key, slot = jax.lax.sort(
            (local, jnp.arange(A, dtype=jnp.int32)), num_keys=1)
        # Whole windows: the last one is filled up with assignments
        # held elsewhere, like the ones that sort there anyway.
        fill = -A % bound
        key = jnp.pad(key, (0, fill), constant_values=eh)
        slot = jnp.pad(slot, (0, fill), constant_values=A)
        first = jnp.cumsum(counts) - counts
        weight = w.reshape(A)

        def window(i, y):
            lo = i * bound
            # What of each expert's run of the sorted order lies inside.
            inside = jnp.clip(first + counts, lo, lo + bound) \
                - jnp.clip(first, lo, lo + bound)
            padded = -(-inside // tm) * tm
            p_end = jnp.cumsum(padded)
            start = jnp.cumsum(inside) - inside
            ky = jax.lax.dynamic_slice(key, (lo,), (bound,))
            sl = jax.lax.dynamic_slice(slot, (lo,), (bound,))
            kc = jnp.minimum(ky, eh - 1)
            dest = jnp.where(
                ky < eh,
                (p_end - padded)[kc] + jnp.arange(bound) - start[kc], rows)
            src_tok = jnp.zeros((rows,), jnp.int32).at[dest].set(
                sl // k, mode="drop")
            ys = self._expert_rows(params, xf[src_tok], padded, p_end, tm,
                                   serving)
            # The window's held assignments back in (token, choice)
            # order, each with its row; the others sort last and are
            # masked (not multiplied, as above).
            sl, row = jax.lax.sort(
                (jnp.where(ky < eh, sl, A), jnp.minimum(dest, rows - 1)),
                num_keys=1)
            held = sl < A
            sl = jnp.minimum(sl, A - 1)
            terms = jnp.where(
                held[:, None],
                ys[row].astype(jnp.float32) * weight[sl][:, None], 0.0)
            return y.at[sl // k].add(terms, indices_are_sorted=True)

        return jax.lax.fori_loop(
            0, -(-jnp.sum(counts) // bound), window,
            jnp.zeros((T, d), jnp.float32))

    def _expert_rows(self, params, xs, padded, p_end, tm, serving):
        """``xs`` (rows, d), sorted by expert and padded to whole tiles
        of ``tm`` rows an expert (``padded`` rows each, ending at
        ``p_end``), through the routed experts' MLPs: ``ys`` (rows, d);
        rows past ``p_end[-1]`` may hold anything."""
        a = self.attrs
        rows, d = xs.shape
        eh, f = len(self.held), a["ffn_dim"]
        if serving and pallas_kernels.grouped_matmul_supported(d, f, xs.dtype) \
                and pallas_kernels.grouped_matmul_supported(f, d, xs.dtype):
            n_tiles = rows // tm
            used = p_end[-1] // tm
            tile_e = jnp.sum(
                p_end[None, :] <= (jnp.arange(n_tiles) * tm)[:, None], axis=1)
            last_e = jnp.minimum(tile_e[jnp.maximum(used - 1, 0)], eh - 1)
            tile_e = jnp.where(jnp.arange(n_tiles) < used, tile_e, last_e)

            def product(x, w, w_up=None):
                return pallas_kernels.grouped_matmul(
                    x, w, tile_e, used, tm, w_up=w_up)

            fused_gate = product
        else:
            fused_gate = None

            def product(x, w):
                return jax.lax.ragged_dot(x, w, padded)

        routed = ("w_gate", "w_up", "w_down") if a["gated"] else ("w1", "w2")
        return self._mlp(xs, params, routed, product, fused_gate)
