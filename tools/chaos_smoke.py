#!/usr/bin/env python
"""Chaos smoke: the resilience fault matrix in a fresh CPU subprocess.

Runs every scenario in ``flexflow_tpu/runtime/chaos.py`` — raised
fault / NaN batch / NaN loss inside a k=8 superstep, SIGTERM
preemption + resume, checkpoint corruption fallback,
kill-between-force-save-phases — each required to finish with a loss
trajectory bit-identical to the unfaulted run — plus the serving
fault scenarios (SERVING.md): fault isolation (NaN logits / raised
exception inside a decode superstep: the faulted request errors out,
surviving slots' sequences byte-identical), overload shedding,
``serving_engine_crash`` (journaled crash recovery: engine-class
fault kills / in-process-restarts the scheduled server, journal
replay resumes byte-identically, padded AND paged) and
``serving_sigterm_drain`` (drain-on-SIGTERM: in-flight work journaled
at the fence, clean exit, resume byte-identical) and
``serving_spec_fault`` (faults inside the speculative draft+verify
round: faulted slots error at the verify fence, survivors
byte-identical to the UNSPECULATED run, padded AND paged) and
``prefix_donor_eviction`` (prefix sharing: the donor of a shared
KV block crashes mid-decode — refcounts keep the block alive, the
content-hash index survives, sharers byte-identical to the unshared
run; padded oracle AND paged cache-off sub-checks; SERVING.md
"Prefix sharing") and
``replica_loss`` (fleet: a replica engine-fault exhausts its restart
budget, the router redistributes its journaled in-flight requests to
the survivor, merged output byte-identical to the single-replica run,
padded AND paged; SERVING.md "Fleet") — and the multi-host world
failures, ``host_loss`` and ``coordinator_loss``, on the live
2-process ``jax.distributed`` rig (RESILIENCE.md "Host loss & elastic
resize": launcher-classified kill, elastic resize / same-world
coordinator restart, post-recovery trajectory bit-identical).
<2 min on the 8-device virtual CPU mesh; never takes the chip (the
parent stays off jax, the child is pinned to ``JAX_PLATFORMS=cpu``).

Usage: python tools/chaos_smoke.py [scenario ...]
Exit code 0 iff every scenario passed.
"""

import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parent(argv):
    """Re-exec in a clean CPU subprocess (fresh backend, 8-dev mesh)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    return subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--child"] + argv,
        env=env,
    )


def child(argv):
    from flexflow_tpu.runtime.chaos import SCENARIOS, run_matrix

    names = [a for a in argv if not a.startswith("-")] or None
    if names:
        unknown = set(names) - set(SCENARIOS)
        if unknown:
            print(f"unknown scenarios: {sorted(unknown)} "
                  f"(have: {list(SCENARIOS)})", file=sys.stderr)
            return 2
    import time

    t0 = time.perf_counter()
    failures = n = 0
    with tempfile.TemporaryDirectory(prefix="chaos_smoke_") as root:
        # One run_matrix call per scenario so each row carries its own
        # wall time (the rig baseline cache in chaos.py persists across
        # calls, so the split costs nothing).
        for name in (names or list(SCENARIOS)):
            ts = time.perf_counter()
            results = run_matrix(root, [name])
            dt = time.perf_counter() - ts
            for ok, rname, detail in results:
                print(f"{'PASS' if ok else 'FAIL'}  {rname:<22} "
                      f"{dt:6.1f}s  {detail}")
                failures += 0 if ok else 1
                n += 1
    print(f"chaos matrix: {n - failures}/{n} passed "
          f"in {time.perf_counter() - t0:.1f}s")
    return 1 if failures else 0


def main():
    argv = sys.argv[1:]
    if "--child" in argv:
        argv.remove("--child")
        return child(argv)
    return parent(argv)


if __name__ == "__main__":
    sys.exit(main())
