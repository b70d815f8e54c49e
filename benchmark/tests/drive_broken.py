#!/usr/bin/env python3
"""Drive one run of a copy of the benchmark with the timed path broken
underneath, skipping the harness's look for a chip.

    python3 drive_broken.py <root of the copy> <cell> <frozen_step|altered_token>

``frozen_step``: the program's train step returns its state unchanged
(its loss is still the batch's).  ``altered_token``: one served token of
every request is altered where the server hands its results back.
The run's last line must then say ``"correct": false``.
"""

import sys


def main() -> int:
    root, cell, fault = sys.argv[1:4]
    sys.path.insert(0, root)
    from benchmark import common, run

    common.require_device = lambda chips: {"platform": "cpu", "kind": "cpu", "count": chips}

    if fault == "frozen_step":
        from flexflow_tpu.runtime.executor import Executor

        def frozen(self):
            def step(params, opt_state, state, batch):
                _loss, metrics = self.eval_step(params, state, batch)
                return params, opt_state, state, metrics
            return step

        Executor.train_step = property(frozen)
    elif fault == "altered_token":
        from flexflow_tpu.runtime.serving import Server

        real = Server.run

        def altered(self, requests):
            results, stats = real(self, requests)
            for r in results.values():
                mid = len(r.tokens) // 2
                r.tokens[mid] = (r.tokens[mid] + 1) % 251
            return results, stats

        Server.run = altered
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return run.main(["--workload", cell, "--seed", "11", "--seconds", "1", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
