"""Pallas TPU kernels for the SVC compute hot spots.

Three kernels cover the maintenance/estimation inner loops that dominate
the paper's profiles (§7: hashing + delta aggregation + estimation):

  hash_threshold  — η_{a,m}: splitmix32 key hashing + threshold mask (VPU)
  segment_aggsum  — group-by partial aggregation as one-hot × values matmul
                    (MXU-native group-by; the TPU adaptation of hash groups)
  corr_diff       — fused correspondence-subtract + moment accumulation
                    (the SVC+CORR inner loop: Σd, Σd², count in one pass)
  fused_clean     — η hashing + threshold + group-by sum/count in ONE pass
                    over delta rows (no materialized filtered intermediate);
                    core/maintenance.clean_sample dispatches to it when the
                    cleaning plan has the canonical groupby-sum/count shape
  multi_agg       — batched-query moment pass: one scan over the
                    correspondence-aligned sample panel accumulates the
                    masked weighted sums/counts/sum-of-squares/HT terms for
                    ALL Q queries of an encoded QueryBatch (repro.query),
                    including the pin-aware HT_D diff-variance row (§6.3)
  outlier_member  — fused η ∨ outlier-index membership (§6.2): the shared
                    splitmix32 mixer folds key columns into the η hash and
                    a 64-bit (hi, lo) membership digest in one pass;
                    membership resolves by sorted-digest binary search
                    (XLA) or a VMEM-resident digest-table compare (Pallas)
  flash_attention — causal online-softmax attention (GQA/MQA aware): the
                    §Roofline memory-term lever — scores stay in VMEM

Each kernel ships ``kernel.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling), ``ops.py`` (jit'd padding/reshaping wrapper), and ``ref.py``
(pure-jnp oracle).  Tests sweep shapes/dtypes against the oracle.  Which
path an op takes is decided at its first call by ``platform.py``: Pallas
compiled by Mosaic on a TPU, interpret mode only off it.

Call ``enable()`` to route repro.core.hashing through the Pallas path.

Profiling: every ops.py dispatch funnels through
``repro.obs.kprof.profiled(op, fn, ...)``.  Install a ``KernelProfiler``
(re-exported here with ``set_profiler``/``get_profiler``) to record
per-op dispatch counts, fallback-path takes, compile vs. execute wall,
and padded-vs-real row occupancy; with no profiler installed the hook is
a tail call with zero added work.
"""

from repro.obs.kprof import KernelProfiler, get_profiler, set_profiler  # noqa: F401


def enable() -> None:
    from repro.core import hashing

    hashing.use_pallas(True)


def disable() -> None:
    from repro.core import hashing

    hashing.use_pallas(False)
