#!/usr/bin/env python3
"""Drive one run of a copy of the benchmark with the convolution windows
lost in the program's place, while the reference keeps them.

    python3 drive_stateless_conv.py <root of the copy> <cell> <window_zeroed|window_at_bucket_end> \
        [--seed N] [--seconds S] [--cpu]

``window_zeroed``: every decode step of every gated short convolution
reads a window of zeros (and writes zeros back), so a decoded token's
``c_t`` is its newest tap alone, as if the op kept no state.
``window_at_bucket_end``: a prefill hands on the window that ends at its
padded bucket's end and not at the prompt's length, so the first decode
steps convolve over pad rows.

Whether the run's last line then says ``"correct": false`` is what the
caller is here to find out: it shows whether the judged number
(``served_logit_gap``) can see the fault.  The reference's own control
(fp8 products) is ``chip_limits.py``'s.  ``--cpu`` skips the harness's
look for a chip (the rehearsal).
"""

import sys


def main() -> int:
    root, cell, fault, *rest = sys.argv[1:]
    sys.path.insert(0, root)
    from benchmark import common, run

    if "--cpu" in rest:
        rest.remove("--cpu")
        common.require_device = lambda chips: {"platform": "cpu", "kind": "cpu", "count": chips}
    opts = {"--seed": "11", "--seconds": "1"}
    opts.update(zip(rest[::2], rest[1::2]))

    import jax.numpy as jnp

    from flexflow_tpu.ops.short_conv import GatedShortConv

    real = GatedShortConv.forward
    if fault == "window_zeroed":
        def lost(self, params, xs, state, training):
            if "cache_conv" in state and xs[0].shape[1] == 1:
                state = dict(state, cache_conv=jnp.zeros_like(state["cache_conv"]))
                ys, new = real(self, params, xs, state, training)
                return ys, dict(new, cache_conv=jnp.zeros_like(new["cache_conv"]))
            return real(self, params, xs, state, training)
    elif fault == "window_at_bucket_end":
        def lost(self, params, xs, state, training):
            return real(self, params, xs,
                        {k: v for k, v in state.items() if k != "length"}, training)
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    GatedShortConv.forward = lost
    return run.main(["--workload", cell, "--seed", opts["--seed"], "--seconds", opts["--seconds"],
                     "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
