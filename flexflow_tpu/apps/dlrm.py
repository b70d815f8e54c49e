"""DLRM app (reference: ``examples/DLRM/dlrm.cc``).

Accepts the reference's ``--arch-*`` flags (``dlrm.cc:169-224``) on top
of the common FFConfig surface, places embedding tables with the
reference's table-parallel strategy by default, and prints the
``THROUGHPUT = ... samples/s`` line (``dlrm.cc:165-166``).

Example (README's shape: 4 of ``run_random.sh``'s 8 tables, at its
widths)::

    python -m flexflow_tpu.apps.dlrm -b 1024 -i 20 \
        --arch-sparse-feature-size 64 \
        --arch-embedding-size 1000000-1000000-1000000-1000000 \
        --arch-mlp-bot 64-512-512-64 --arch-mlp-top 320-1024-1024-1024-1

DLRM-specific flags:
  --prod-trace          stream a production-shaped synthetic trace
                        (power-law-skewed embedding ids + bursty
                        arrival; data/trace.py) — implies
                        --stream-dataset.  Named --prod-trace because
                        --trace DIR is the XProf capture flag.
  --trace-alpha F       zipf skew of the trace ids (default 1.2, > 1)
  --trace-burst S       pause S seconds every 16th chunk read (bursty
                        arrival; default 0 = smooth)
With -d PATH --stream-dataset, the Criteo HDF5 is read in chunks
through CriteoStreamSource (never host-materialized; DATA.md).
"""

from __future__ import annotations

import sys

from flexflow_tpu.apps.common import (
    check_help,
    load_strategy,
    pop_float,
    run_training,
)
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm, dlrm_strategy


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    check_help(argv, __doc__)
    prod_trace = "--prod-trace" in argv
    if prod_trace:
        argv.remove("--prod-trace")
    trace_alpha = pop_float(argv, "--trace-alpha", 1.2)
    trace_burst = pop_float(argv, "--trace-burst", 0.0)
    cfg = FFConfig.parse_args(argv)
    if prod_trace:
        # The trace generator only exists as a StreamSource.
        cfg.stream_dataset = True
    if any(a.startswith("--arch-") for a in argv):
        dlrm = DLRMConfig.parse_args(argv)
    else:
        # The reference's header defaults (dlrm.h:23-32) are mutually
        # inconsistent (top MLP width != interaction width) because the
        # run scripts always pass --arch-*; default to a small
        # consistent shape instead: 4 tables x 1000 rows, 16-dim.
        dlrm = DLRMConfig(
            sparse_feature_size=16,
            embedding_size=[1000] * 4,
            mlp_bot=[16, 64, 16],
            mlp_top=[16 + 4 * 16, 64, 1],
        )
    ff = build_dlrm(batch_size=cfg.batch_size, dlrm=dlrm, config=cfg)
    ndev = cfg.resolve_num_devices()
    strategy = load_strategy(cfg, ndev) or dlrm_strategy(
        ndev, dlrm, shard_embeddings=cfg.shard_embeddings)
    int_high = {"sparse_input": min(dlrm.embedding_size)}
    arrays = None
    stream_source = None
    num_samples = cfg.batch_size * max(cfg.iterations, 1) * 2
    if prod_trace and not cfg.dry_run:
        if cfg.dataset_path:
            raise SystemExit("--prod-trace and -d are mutually exclusive")
        if len(set(dlrm.embedding_size)) != 1:
            raise SystemExit(
                "--prod-trace emits one stacked sparse_input tensor, "
                "which needs uniform --arch-embedding-size vocabs"
            )
        from flexflow_tpu.data.trace import ProductionTraceSource

        stream_source = ProductionTraceSource(
            num_samples, dense_dim=dlrm.mlp_bot[0],
            vocab_sizes=list(dlrm.embedding_size), alpha=trace_alpha,
            seed=cfg.seed,
            burst_every=16 if trace_burst > 0 else 0,
            burst_s=trace_burst,
        )
    elif cfg.dataset_path and not cfg.dry_run:
        if cfg.stream_dataset:
            # Chunked out-of-core reads straight off the HDF5 — the
            # dataset never materializes on the host (DATA.md).
            from flexflow_tpu.data.criteo import CriteoStreamSource

            stream_source = CriteoStreamSource(
                cfg.dataset_path, dlrm, max_samples=num_samples,
            )
        else:
            # The reference's Criteo HDF5 schema (dlrm.cc:239-281).
            from flexflow_tpu.data.criteo import make_dlrm_arrays

            arrays = make_dlrm_arrays(
                dlrm, num_samples=num_samples, path=cfg.dataset_path,
            )
    # The data-tier flags need a real dataset to tier: forward
    # num_samples so synthetic arrays materialize and flow through the
    # loader (--zc-dataset then stages device-resident and its
    # FF_DEVICE_MEM_BYTES capacity check — which counts the per-device
    # table bytes — actually runs).  The default path keeps the
    # reference's fixed syntheticInput batch.
    synth_n = num_samples if (cfg.zc_dataset or cfg.stream_dataset) \
        else None
    run_training(ff, cfg, strategy=strategy, int_high=int_high,
                 num_samples=synth_n,
                 arrays=arrays, stream_source=stream_source)
    return 0


if __name__ == "__main__":
    sys.exit(main())
