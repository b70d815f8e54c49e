"""Serving latency model: modeled prefill/decode costs in virtual ms.

The SEARCH.md cost-model discipline applied to serving: dispatch and
fence constants come from :class:`flexflow_tpu.search.cost_model.
Calibration` (fitted on a run's own JSONL, or the uncalibrated
defaults), and the per-token compute slopes are fitted from a SERVING
run's own ``prefill`` / ``decode_superstep`` events when one is
available (:meth:`ServingLatencyModel.fit_events`).

Program shapes being priced (runtime/serving.py):

- prefill bucket L: one dispatch + one fence + L tokens of
  full-sequence forward -> ``dispatch_ms + fence_ms + L * prefill_token_ms``
- decode superstep k: one dispatch + one fence + k fused single-token
  steps over the whole slot batch ->
  ``dispatch_ms + fence_ms + k * decode_token_ms``
  (batch-width-free: the batch dim rides inside the one program).
- speculative round d: one dispatch + one fence + d+1 draft steps on
  the truncated model (the +1 primes the draft cache at the verify
  token's row) + d+1 verify steps on the full model ->
  ``dispatch_ms + fence_ms + (d + 1) * draft_token_ms
  + (d + 1) * decode_token_ms`` — the verify scan IS the decode
  superstep body, so its slope is ``decode_token_ms``; only the cheap
  draft chain gets its own slope.  Draft prefill (one per admission in
  spec mode) prices like a prefill of the same bucket.

The scheduler's virtual clock advances by exactly these quantities, so
"predicted" and "scheduled" time are the same number by construction —
the honest currency is the DISPATCH/FENCE COUNT, which the telemetry
accounting audits exactly (tests/test_serving_sched.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Optional

#: Fallback per-token slopes (virtual ms) when no serving run has been
#: fitted yet — placeholders, small next to the dispatch constants;
#: none of them is measured on the chip (ROADMAP A2, A5).
DEFAULT_PREFILL_TOKEN_MS = 0.05
DEFAULT_DECODE_TOKEN_MS = 0.2
#: Draft steps run the truncated (or small) model — cheaper than a
#: full decode step, costlier than free.
DEFAULT_DRAFT_TOKEN_MS = 0.1


@dataclasses.dataclass
class ServingLatencyModel:
    dispatch_ms: float
    fence_ms: float
    prefill_token_ms: float = DEFAULT_PREFILL_TOKEN_MS
    decode_token_ms: float = DEFAULT_DECODE_TOKEN_MS
    draft_token_ms: float = DEFAULT_DRAFT_TOKEN_MS
    #: Prefix-cache behaviour observed in the fitted run (SERVING.md
    #: "Prefix sharing"): fraction of admissions that adopted a
    #: resident prefix, and the mean token span a hit skipped.  Both
    #: default 0.0 — :meth:`expected_prefill_ms` then equals
    #: :meth:`prefill_ms`, so uncalibrated decisions are unchanged.
    prefix_hit_rate: float = 0.0
    prefix_mean_offset: float = 0.0
    calibrated: bool = False
    source: Optional[str] = None

    # -- the program prices --------------------------------------------------

    def prefill_ms(self, bucket: int, offset: int = 0) -> float:
        """``offset`` is the prefix-sharing offset prefill's skipped
        span (SERVING.md "Prefix sharing"): the program computes only
        ``bucket - offset`` token positions behind the same one
        dispatch + one fence."""
        return self.dispatch_ms + self.fence_ms + \
            max(bucket - offset, 0) * self.prefill_token_ms

    def expected_prefill_ms(self, bucket: int) -> float:
        """The prefix-cache-aware EXPECTED prefill price: the bucket's
        token span discounted by the fitted hit rate × mean skipped
        offset.  An ESTIMATE for routing / preemption-worth decisions
        only — the virtual clock always advances by the exact
        :meth:`prefill_ms` of the program actually built, so using
        this in estimates never perturbs dispatch accounting."""
        saved = self.prefix_hit_rate * self.prefix_mean_offset
        return self.dispatch_ms + self.fence_ms + \
            max(bucket - saved, 0.0) * self.prefill_token_ms

    def decode_ms(self, k: int) -> float:
        return self.dispatch_ms + self.fence_ms + k * self.decode_token_ms

    def spec_ms(self, d: int) -> float:
        """One speculative round: d+1 draft + d+1 verify steps fused
        behind one dispatch/fence pair."""
        return self.dispatch_ms + self.fence_ms + \
            (d + 1) * self.draft_token_ms + (d + 1) * self.decode_token_ms

    def draft_prefill_ms(self, bucket: int) -> float:
        """Draft-cache prefill at admission (spec mode only): a
        second prefill-shaped dispatch over the truncated model —
        priced like the full prefill (conservative)."""
        return self.prefill_ms(bucket)

    def describe(self) -> str:
        tag = f"calibrated from {self.source}" if self.calibrated else \
            "uncalibrated defaults"
        prefix = ""
        if self.prefix_hit_rate:
            prefix = (f", prefix hit {self.prefix_hit_rate:.2f} × "
                      f"{self.prefix_mean_offset:.1f} tok")
        return (f"serving latency model ({tag}): dispatch "
                f"{self.dispatch_ms:.3f} + fence {self.fence_ms:.3f} ms, "
                f"prefill {self.prefill_token_ms:.4f} ms/token, decode "
                f"{self.decode_token_ms:.4f} ms/token, draft "
                f"{self.draft_token_ms:.4f} ms/token{prefix}")

    def to_json(self) -> Dict[str, Any]:
        return {
            "dispatch_ms": round(self.dispatch_ms, 4),
            "fence_ms": round(self.fence_ms, 4),
            "prefill_token_ms": round(self.prefill_token_ms, 5),
            "decode_token_ms": round(self.decode_token_ms, 5),
            "draft_token_ms": round(self.draft_token_ms, 5),
            "prefix_hit_rate": round(self.prefix_hit_rate, 4),
            "prefix_mean_offset": round(self.prefix_mean_offset, 3),
            "calibrated": self.calibrated,
            "source": self.source,
        }

    # -- constructors --------------------------------------------------------

    @staticmethod
    def from_calibration(cal=None) -> "ServingLatencyModel":
        """Dispatch/fence constants from an execution-search
        :class:`Calibration` (None = the uncalibrated defaults);
        per-token slopes stay at the defaults until a serving run is
        fitted on top (:meth:`fit_events`)."""
        if cal is None:
            from flexflow_tpu.search.cost_model import Calibration

            cal = Calibration()
        return ServingLatencyModel(
            dispatch_ms=float(cal.dispatch_ms),
            fence_ms=float(cal.fence_ms),
            calibrated=bool(cal.calibrated),
            source=cal.source,
        )

    def fit_events(self, events: Iterable[Any],
                   source: Optional[str] = None) -> "ServingLatencyModel":
        """Fit the per-token slopes from a serving run's own raw
        events (``prefill`` carries ``bucket``/``wall_s``;
        ``decode_superstep`` carries ``k``/``wall_s``; ``spec_verify``
        carries ``d``/``wall_s``): slope = median of ``(wall_ms -
        dispatch_ms - fence_ms) / tokens``, floored at 0 — one robust
        point per event, no regression machinery.  The draft slope is
        the spec-round residual AFTER the (possibly just-fitted)
        decode slope prices the d+1 verify steps.  ``prefix_hit``
        events (no ``wall_s`` — full hits run no program) fit the
        prefix terms: hit rate over admissions (``prefill`` events +
        full hits) and the mean ``tokens_saved`` per hit, feeding
        :meth:`expected_prefill_ms`.  Returns a NEW
        model; self is untouched."""
        pf, dc, sp = [], [], []
        admissions = hits = 0
        saved_total = 0.0
        overhead = self.dispatch_ms + self.fence_ms
        for ev in events:
            kind = ev.get("ev")
            if kind == "prefix_hit":
                hits += 1
                saved_total += float(ev.get("tokens_saved") or 0)
                if ev.get("full"):
                    # Full hits never emit a prefill event — they are
                    # admissions all the same.
                    admissions += 1
                continue
            wall = ev.get("wall_s")
            if wall is None:
                continue
            wall_ms = float(wall) * 1e3
            if kind == "prefill" and ev.get("bucket"):
                admissions += 1
                if ev.get("offset"):
                    # Prefix-sharing offset prefills computed fewer
                    # tokens than the bucket — folding them in would
                    # bias the slope low.
                    continue
                pf.append(max(wall_ms - overhead, 0.0)
                          / float(ev["bucket"]))
            elif kind == "decode_superstep" and ev.get("k"):
                dc.append(max(wall_ms - overhead, 0.0) / float(ev["k"]))
            elif kind == "spec_verify" and ev.get("d"):
                sp.append((float(ev["d"]), max(wall_ms - overhead, 0.0)))

        def med(xs, default):
            if not xs:
                return default
            xs = sorted(xs)
            return xs[len(xs) // 2]

        decode_slope = med(dc, self.decode_token_ms)
        draft = med(
            [max(w - (d + 1) * decode_slope, 0.0) / (d + 1) for d, w in sp],
            self.draft_token_ms,
        )
        return ServingLatencyModel(
            dispatch_ms=self.dispatch_ms,
            fence_ms=self.fence_ms,
            prefill_token_ms=med(pf, self.prefill_token_ms),
            decode_token_ms=decode_slope,
            draft_token_ms=draft,
            prefix_hit_rate=(hits / admissions) if admissions
            else self.prefix_hit_rate,
            prefix_mean_offset=(saved_total / hits) if hits
            else self.prefix_mean_offset,
            calibrated=self.calibrated or bool(pf or dc or sp),
            source=source or self.source,
        )

    @staticmethod
    def from_run(run, cal=None) -> "ServingLatencyModel":
        """Constants from ``cal`` (or the run's own calibration block)
        + slopes fitted from the run's serving events.  ``run`` is an
        ``obs.reader.RunLog``."""
        if cal is None:
            from flexflow_tpu.search.cost_model import Calibration

            block = run.calibration()
            cal = Calibration.from_summary(block, source=run.path) \
                if block else Calibration()
        base = ServingLatencyModel.from_calibration(cal)
        return base.fit_events(run.iter_raw(), source=run.path)
