#!/usr/bin/env python3
"""Drive one run of a copy of the benchmark with the window broken in
the program's place, while the reference keeps it.

    python3 drive_unwindowed.py <root of the copy> <cell> <window_off|ring_off_by_one> \
        [--seed N] [--seconds S] [--slots N] [--cpu]

``window_off``: every attention op is built without its ``window``, so
the Laguna family's window layers attend their whole causal past through
full caches, in the prefill and in every decode step (five full caches
are 2.5 times the cell's bytes: ``--slots`` serves fewer callers so that
they fit; the judged number does not depend on the slots).
``ring_off_by_one``: a decode step writes its position one row further
round the ring than the prefill's install put its neighbours, so every
step overwrites a position its window still holds and keeps one it has
left.

Whether the run's last line then says ``"correct": false`` is what the
caller is here to find out: it shows whether the judged number
(``served_logit_gap``) can see the fault.  ``--cpu`` skips the harness's
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
    if "--slots" in opts:
        real_cell = common.load_cell

        def fewer(bench, workload):
            out = real_cell(bench, workload)
            out[2]["slots"] = int(opts["--slots"])
            return out

        common.load_cell = fewer

    from flexflow_tpu.ops.attention import MultiHeadAttention

    if fault == "window_off":
        real = MultiHeadAttention.__init__

        def unwindowed(self, *args, window=None, **kw):
            real(self, *args, **kw)

        MultiHeadAttention.__init__ = unwindowed
    elif fault == "ring_off_by_one":
        from jax import lax

        real_index = MultiHeadAttention._ring_index

        def shifted(self, pos):
            _at, live = real_index(self, pos)
            return lax.rem(pos + 1, self.attrs["window"]), live

        MultiHeadAttention._ring_index = shifted
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return run.main(["--workload", cell, "--seed", opts["--seed"], "--seconds", opts["--seconds"],
                     "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
