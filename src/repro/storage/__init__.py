"""External-memory storage subsystem: the paper's E2LSH-on-Storage, real.

``repro.core.storage`` holds the ANALYTICAL cost model (Eq. 6/7, device
tables); this package holds the EXECUTABLE storage tier it predicts:

* ``format``     — the versioned, page-aligned on-disk spill format
  (``IndexArrays.spill`` / ``load_external``);
* ``blockstore`` — pluggable block-read backends
  (``mem``/``mmap``/``aio``/``uring``) with the measured-N_io ledger and
  the shared clock page cache (``REPRO_STORE_BACKEND`` forces a lane);
* ``uring``      — the real async I/O engine: O_DIRECT aligned reads at
  queue depth, submitted as io_uring waves via ctypes or, without
  io_uring, from a ``preadv`` pool, with the runtime ``capabilities()``
  probe that gates it;
* ``external``   — ``plan="external"``: device hash/plan + host block
  fetches + device distance epilogue, with per-rung overlap stats;
* ``measure``    — the measured sync-vs-async harness (cold-cache
  methodology + the QD/block-size sweep) shared by
  ``benchmarks/sync_vs_async.py --measured`` and the BENCH lanes.
"""
from .blockstore import (AioBlockStore, BACKENDS, BlockStore,
                         CachedBlockStore, MemBlockStore, MmapBlockStore,
                         STORE_BACKEND_ENV, StoreStats, make_store,
                         store_backend_env)
from .external import (ExternalIndex, ExternalPlanStats, ExternalPlanTotals,
                       RungStats, external_plan)
from .format import (DIRECT_ALIGN_MIN, FORMAT_VERSION, MAGIC,
                     MANIFEST_MAGIC, MANIFEST_NAME, MANIFEST_VERSION,
                     PAGE_SIZE, SpillHeader, StorageFormatError,
                     aligned_extent, load_arrays, load_arrays_sharded,
                     load_external, load_external_sharded, read_header,
                     read_manifest, spill_index, spill_index_sharded,
                     verify_file)
from .sharded import (ShardedExternalIndex, ShardedExternalPlanStats,
                      StripedBlockStore, sharded_external_plan)
from .measure import (DEFAULT_MODEL_CONFIG, HEAVY_SPEC, SWEEP_QDS,
                      drop_page_cache, heavy_bucket_workload,
                      measure_backends, page_cache_residency, qd_sweep)
from .uring import (IoUring, UringBlockStore, UringUnavailable,
                    capabilities, probe_io_uring, probe_o_direct)

__all__ = [
    "AioBlockStore", "BACKENDS", "BlockStore", "CachedBlockStore",
    "MemBlockStore", "MmapBlockStore", "STORE_BACKEND_ENV", "StoreStats",
    "make_store", "store_backend_env",
    "ExternalIndex", "ExternalPlanStats", "ExternalPlanTotals", "RungStats",
    "external_plan",
    "ShardedExternalIndex", "ShardedExternalPlanStats", "StripedBlockStore",
    "sharded_external_plan",
    "DIRECT_ALIGN_MIN", "FORMAT_VERSION", "MAGIC", "MANIFEST_MAGIC",
    "MANIFEST_NAME", "MANIFEST_VERSION", "PAGE_SIZE",
    "SpillHeader", "StorageFormatError", "aligned_extent", "load_arrays",
    "load_arrays_sharded", "load_external", "load_external_sharded",
    "read_header", "read_manifest", "spill_index", "spill_index_sharded",
    "verify_file",
    "DEFAULT_MODEL_CONFIG", "HEAVY_SPEC", "SWEEP_QDS", "drop_page_cache",
    "heavy_bucket_workload", "measure_backends", "page_cache_residency",
    "qd_sweep",
    "IoUring", "UringBlockStore", "UringUnavailable", "capabilities",
    "probe_io_uring", "probe_o_direct",
]
