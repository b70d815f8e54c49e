#!/usr/bin/env python3
"""Drive one run of a copy of the benchmark with the learned selection
switched off in the program's place: the selector keeps every live
position (its ``topk`` raised past any sequence), so each attention
layer of the Keye-VL-2.0 family attends its whole causal past, in the
prefill and in every decode step, while the reference still selects.

    python3 drive_unselected.py <root of the copy> <cell> [--seed N] [--seconds S] [--cpu]

Whether the run's last line then says ``"correct": false`` is what the
caller is here to find out: it shows whether the judged number
(``served_logit_gap``) can see a selector that does not select.
``--cpu`` skips the harness's look for a chip (the rehearsal).
"""

import sys


def main() -> int:
    root, cell, *rest = sys.argv[1:]
    sys.path.insert(0, root)
    from benchmark import common, run

    if "--cpu" in rest:
        rest.remove("--cpu")
        common.require_device = lambda chips: {"platform": "cpu", "kind": "cpu", "count": chips}

    from flexflow_tpu.ops.token_select import TokenSelector

    real = TokenSelector.__init__

    def everything(self, *args, **kw):
        real(self, *args, **kw)
        self.topk = 1 << 30

    TokenSelector.__init__ = everything
    opts = {"--seed": "11", "--seconds": "1"}
    opts.update(zip(rest[::2], rest[1::2]))
    return run.main(["--workload", cell, "--seed", opts["--seed"], "--seconds", opts["--seconds"],
                     "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
