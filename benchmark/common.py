"""What every runner shares: files, the device, the compile cache, the
compile counter, seeded parameters in the program's own tree, and the
numbers a training comparison reads.  No cell, model or metric is named
here: they arrive as data."""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: Everything a run leaves behind goes here (listed in .gitignore): the
#: compile cache at a fixed path (the path is part of the cache's key),
#: the telemetry stream, the profiler's trace.
SCRATCH = os.path.join(ROOT, ".bench_scratch")
CACHE_DIR = os.path.join(SCRATCH, "jax_cache")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def read_events(path: str) -> List[Dict[str, Any]]:
    """The telemetry stream ``path`` (JSONL), one event a line."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name a data file gives."""
    if not name.replace("_", "").isalnum():
        raise SystemExit(f"bad {kind} name {name!r}")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def load_cell(bench: Dict[str, Any], workload: str):
    """``(cell, config, traffic, runner, family)`` of the manifest's cell
    ``workload``: its entry, the two files it names, and the modules
    those name.  Importing the runner and the family imports the program."""
    from benchmark import manifest

    cell = manifest.entry(bench["workloads"], workload, "workload")
    config = load_json(ROOT, manifest.entry(bench["configs"], cell["config"], "config")["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return (cell, config, traffic, load_module("runners", traffic["kind"]),
            load_module("families", config["family"]))


def say(msg: str) -> None:
    print(msg, flush=True)


def enable_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed path in
    the checkout.  Every program is cached, however fast it compiled."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env or CACHE_DIR


def require_device(chips: int) -> Dict[str, Any]:
    """The device as jax reports it.  No TPU, or fewer chips than the
    cell asks for, is an error with no result line — unless the CPU was
    asked for by name (``JAX_PLATFORMS=cpu``), which is a rehearsal
    whose line names the CPU."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if platform != "tpu" and not rehearsal:
        print(f"benchmark: jax found platform {platform!r}, not a TPU; nothing was run",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chips, jax found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": platform, "kind": devs[0].device_kind, "count": chips}


class CompileCounter:
    """Programs built (compiled, or loaded from the cache) while ``armed``."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if self.armed and name == _COMPILE_EVENT:
            self.count += 1

    @contextlib.contextmanager
    def window(self):
        self.armed = True
        try:
            yield self
        finally:
            self.armed = False


def peak_memory_bytes(devices) -> int:
    """Peak bytes on the fullest chip (0 where the backend does not
    report it, as on the CPU): the allocator's reserved peak, which holds
    a running program's temporaries, where it is reported, else the peak
    of live buffers.  Both go on a line of their own."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"[memory] {d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"peak_bytes_reserved={stats.get('peak_bytes_reserved')} "
            f"bytes_limit={stats.get('bytes_limit')}")
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak


# -- seeded parameters in the program's tree ---------------------------------


def make_params(spec: Dict[str, Any], seed: int, abstract, shardings):
    """The program's parameter tree ``{op: {key: array}}`` filled from
    the benchmark's recipe ``spec`` (``"op/key" -> (shape, half_width,
    offset)``), on the device, in one jitted call, each leaf in the
    dtype and sharding the program declares for it."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    for op, leaves in abstract.items():
        for key, aval in leaves.items():
            name = f"{op}/{key}"
            if name not in spec:
                raise SystemExit(f"the weight recipe has no leaf {name!r}")
            if tuple(spec[name][0]) != tuple(aval.shape):
                raise SystemExit(
                    f"leaf {name!r}: recipe {tuple(spec[name][0])} != program {tuple(aval.shape)}")
    extra = set(spec) - {f"{o}/{k}" for o, ls in abstract.items() for k in ls}
    if extra:
        raise SystemExit(f"the weight recipe names leaves the program lacks: {sorted(extra)[:4]}")

    def make(seed):
        return {
            op: {
                key: weights.leaf_values(
                    seed, f"{op}/{key}", aval.shape, spec[f"{op}/{key}"][1],
                    spec[f"{op}/{key}"][2], jnp).astype(aval.dtype)
                for key, aval in leaves.items()
            }
            for op, leaves in abstract.items()
        }

    return jax.jit(make, out_shardings=shardings)(weights.split_seed(seed))


def leaf_norms_fn(spec: Dict[str, Any], seed: int, abstract, shardings):
    """``params -> {"op/key": ||params - seeded||}``, jitted: the seeded
    leaf is made again inside the program, so no copy is kept.  The seed
    is an argument of the program, not a constant in it."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def norms(params, seed):
        out = {}
        for op, leaves in params.items():
            for key, p in leaves.items():
                name = f"{op}/{key}"
                p0 = weights.round_to(weights.leaf_values(
                    seed, name, p.shape, spec[name][1], spec[name][2], jnp), p.dtype.name, jnp)
                d = p.astype(jnp.float32) - p0
                out[name] = jnp.sqrt(jnp.sum(jnp.square(d)))
        return out

    f = jax.jit(norms, in_shardings=(shardings, None))
    return lambda params: {k: float(v) for k, v in jax.device_get(
        f(params, weights.split_seed(seed))).items()}


def tree_norms(tree) -> Dict[str, float]:
    """``{"op/key": l2 norm}`` of a ``{op: {key: array}}`` tree."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda t: {
        f"{op}/{k}": jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for op, ls in t.items() for k, v in ls.items()})
    return {k: float(v) for k, v in jax.device_get(f(tree)).items()}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float], skip=()) -> float:
    """Largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero).
    Leaves in ``skip`` are left out."""
    med = float(np.median([want[k] for k in want]))
    worst, at = 0.0, None
    for k, w in want.items():
        gap = abs(got[k] - w) / max(w, med, 1e-30)
        if k not in skip and gap > worst:
            worst, at = gap, k
    if at is not None:
        say(f"[check] worst leaf {at}: program {got[at]:.6g} reference {want[at]:.6g} median leaf {med:.6g}")
    return worst


def noise_leaves(grad_norms: Dict[str, float], floor: float = 1e-3):
    """Leaves whose first gradient, in the reference, is under ``floor``
    of the median leaf's: round-off, not signal (a key bias shifts every
    score of a softmax row alike, so its true gradient is zero).  Adam
    steps such a leaf by the sign of the noise, so its change says
    nothing about the program."""
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v < floor * med}


def stamp(t0: float, what: str) -> None:
    say(f"[time] {time.time() - t0:8.2f} s  {what}")


class Check:
    """The numbers compared, each beside its limit; ``correct`` is all
    of them inside."""

    def __init__(self):
        self.rows: List[tuple] = []

    def add(self, name: str, value: float, limit: float) -> None:
        ok = bool(np.isfinite(value)) and value <= limit
        self.rows.append((name, float(value), float(limit), ok))
        say(f"[check] {name} = {value:.6g}  limit {limit:.6g}  {'ok' if ok else 'OUT'}")

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)
