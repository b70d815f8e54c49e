#!/usr/bin/env python
"""Elastic multi-host training rig (RESILIENCE.md "Host loss & elastic
resize").

Launches an N-process CPU ``jax.distributed`` world — coordinator +
workers, each a FRESH subprocess with its own virtual device slice —
running real training through ``build_hybrid_mesh_plan`` with per-host
loader shards, supervised by ``flexflow_tpu.runtime.elastic.run_rig``:
a SIGKILLed worker is classified ``host_loss`` and the survivors are
relaunched one process smaller against the same checkpoint directory
(elastic resize); a SIGKILLed process 0 is ``coordinator_loss`` and
the same world restarts under a fresh coordinator, within the restart
budget.

Usage:
  python tools/elastic_rig.py --world 2 --ckpt-dir /tmp/rig
  python tools/elastic_rig.py --world 2 --ckpt-dir /tmp/rig \
      --kill-worker-at 11 --telemetry /tmp/rig/tel
  python tools/elastic_rig.py --worker       # one rig process, env-driven

``--worker`` is the per-process entry (``JAX_COORDINATOR_ADDRESS`` /
``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` + ``FF_ELASTIC_*`` in the
environment, exactly what the launcher sets) — the hook for driving
the same protocol from a real multi-node scheduler.

Exit code 0 iff the run completed within the restart budget.  The
launcher never initializes a jax backend itself; it re-execs into a
clean CPU child first so that no accelerator platform named in the
launching environment leaks into the worker tree.
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="run one env-driven rig process (launcher use)")
    ap.add_argument("--world", type=int, default=2,
                    help="initial world size (processes)")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory (required; shared by "
                         "every generation — the elastic handoff point)")
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--k", type=int, default=8,
                    help="steps per superstep dispatch")
    ap.add_argument("--save-every", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--devices-per-host", type=int, default=4)
    ap.add_argument("--kill-worker-at", type=int, default=0, metavar="STEP",
                    help="SIGKILL the last worker at STEP (host_loss)")
    ap.add_argument("--kill-coordinator-at", type=int, default=0,
                    metavar="STEP",
                    help="SIGKILL process 0 at STEP (coordinator_loss)")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--telemetry", default="",
                    help="telemetry dir (one JSONL stream per process "
                         "per generation, -p<id> suffixed)")
    ap.add_argument("--grace", type=float, default=30.0,
                    help="seconds before wedged survivors are reclaimed "
                         "(gloo collectives have no timeout)")
    ap.add_argument("--timeout", type=float, default=420.0)
    return ap.parse_args(argv)


def parent(argv):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--child"] + argv,
        env=env,
    )


def child(argv):
    args = parse_args(argv)
    if args.worker:
        from flexflow_tpu.runtime.elastic import worker_main

        worker_main()  # exits via os._exit, never returns
        return 0
    if not args.ckpt_dir:
        print("--ckpt-dir is required", file=sys.stderr)
        return 2
    if args.kill_worker_at and args.kill_coordinator_at:
        print("--kill-worker-at and --kill-coordinator-at are mutually "
              "exclusive (one fault per rig run)", file=sys.stderr)
        return 2
    from flexflow_tpu.runtime.elastic import RigFailure, run_rig

    kill_process, kill_at = None, 0
    if args.kill_worker_at:
        kill_process, kill_at = args.world - 1, args.kill_worker_at
    elif args.kill_coordinator_at:
        kill_process, kill_at = 0, args.kill_coordinator_at
    try:
        out = run_rig(
            args.world, args.ckpt_dir,
            iters=args.iters, k=args.k, save_every=args.save_every,
            seed=args.seed, global_batch=args.global_batch,
            devices_per_host=args.devices_per_host,
            kill_process=kill_process, kill_at_step=kill_at,
            max_restarts=args.max_restarts,
            telemetry_dir=args.telemetry or None,
            timeout_s=args.timeout, grace_s=args.grace,
        )
    except RigFailure as e:
        print(f"elastic_rig: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out, indent=2, default=str))
    return 0


def main():
    argv = sys.argv[1:]
    if "--child" in argv:
        argv.remove("--child")
        return child(argv)
    # --worker must NOT be re-wrapped: the launcher already built its
    # environment (coordinator address, device count, telemetry).
    if "--worker" in argv:
        return child(argv)
    return parent(argv)


if __name__ == "__main__":
    sys.exit(main())
