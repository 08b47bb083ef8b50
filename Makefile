PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: verify test bench-query bench-smoke deprecation-lane kernel-lane \
	storage-lane uring-lane qos-lane telemetry-lane deps

deps:
	$(PY) -m pip install -r requirements.txt

# tier-1 gate (same command CI runs)
verify:
	$(PY) -m pytest -x -q

test:
	$(PY) -m pytest -q

bench-query:
	$(PY) benchmarks/bench_query_engine.py

# schema-validation pass: 2 repeats, scratch output, asserts the
# BENCH_query.json key layout (CI runs this; publishing numbers stays manual)
bench-smoke:
	$(PY) benchmarks/bench_query_engine.py --smoke

# deprecation firewall, phase 2: the one-PR legacy wrappers are DELETED —
# assert the names stay gone from every public surface (and keep the
# import-time DeprecationWarning escalation for anything new).
deprecation-lane:
	$(PY) -c "import warnings; \
	warnings.filterwarnings('error', category=DeprecationWarning, module=r'repro\..*'); \
	import repro, repro.core, repro.core.distributed, repro.serving, \
	repro.launch.serve, repro.launch.dryrun; \
	import repro.core as c, repro.core.query as q, repro.core.index as i, repro.core.distributed as d; \
	gone = ['query_batch', 'query_batch_fused', 'query_batch_adaptive', \
	'query_batch_adaptive_host', 'ensure_fused_arrays', 'make_query_fn']; \
	leaked = [n for n in gone if hasattr(c, n) or hasattr(q, n)]; \
	leaked += ['sharded_query'] if hasattr(d, 'sharded_query') else []; \
	leaked += ['IndexArrays.from_dict'] if hasattr(i.IndexArrays, 'from_dict') else []; \
	leaked += ['IndexArrays.as_dict'] if hasattr(i.IndexArrays, 'as_dict') else []; \
	leaked += ['E2LSHIndex.as_arrays'] if hasattr(i.E2LSHIndex, 'as_arrays') else []; \
	leaked += ['E2LSHoS.arrays'] if hasattr(c.E2LSHoS, 'arrays') else []; \
	leaked += ['E2LSHoS.fused_arrays'] if hasattr(c.E2LSHoS, 'fused_arrays') else []; \
	leaked += ['SearchEngine.last_external_stats'] if hasattr(c.SearchEngine, 'last_external_stats') else []; \
	assert not leaked, f'deprecated names resurfaced: {leaked}'; \
	print('deprecation lane OK: legacy wrapper names are gone')"

# multi-backend kernel lane (ROADMAP "Multi-backend CI"): pin the Pallas
# kernel path on this backend (interpret mode off-TPU) and run the three
# kernel ops end to end through the fused plan + the queue parity check.
kernel-lane:
	REPRO_FORCE_PALLAS=interpret $(PY) -m pytest \
	tests/test_kernels.py tests/test_force_pallas_lane.py -q

# external-storage lane: spill/load round-trips + plan="external" parity
# (mem/mmap/aio backends over a tmpdir-backed index) under the forced
# interpret kernel path, so the split dispatch runs the REAL kernel
# programs off-TPU; the measured-vs-replay N_io tie-out rides along.
storage-lane:
	REPRO_FORCE_PALLAS=interpret $(PY) -m pytest \
	tests/test_storage_external.py \
	tests/test_io_count.py::test_external_plan_measured_nio_matches_replay -q

# serving-tier lane: the sharded external spill (per-shard files + manifest,
# plan="sharded_external" parity + exact per-shard N_io roll-up) and the QoS
# tick router (priority/EDF packing, deadline shedding with the typed
# DeadlineExceeded, adaptive ladder, cache warming) under the forced
# interpret kernel path. The uring-forced queue test inside gates itself on
# the capability probe, so the lane runs everywhere.
qos-lane:
	REPRO_FORCE_PALLAS=interpret $(PY) -m pytest \
	tests/test_sharded_external.py tests/test_serving_qos.py -q

# telemetry lane: the unified observability layer (docs/telemetry.md) —
# registry exactness under threads, tracer semantics, exporters, the live
# /metrics server, the stats_summary-vs-reset race regression, and the
# trace-vs-ledger consistency tie-out (span-derived read counts must equal
# StoreStats.reads AND the io_count replay on every backend) under the
# forced interpret kernel path so the real plan programs run off-TPU.
telemetry-lane:
	REPRO_FORCE_PALLAS=interpret $(PY) -m pytest tests/test_telemetry.py -q

# async-engine lane: force EVERY make_store call onto the uring backend
# (REPRO_STORE_BACKEND — the storage twin of REPRO_FORCE_PALLAS) and run
# the full parity suite + the N_io tie-out through it. The capability
# probe gates the lane: where neither io_uring (old kernel, seccomp) nor
# O_DIRECT works, the lane prints the probe's reason and skips instead of
# testing the fallback twice.
uring-lane:
	@$(PY) -c "from repro.storage import capabilities; import json, sys; \
	caps = capabilities(); print('capabilities:', json.dumps(caps)); \
	sys.exit(0 if caps['uring_store'] else 3)"; rc=$$?; \
	if [ $$rc -eq 0 ]; then \
		REPRO_STORE_BACKEND=uring $(PY) -m pytest \
		tests/test_storage_external.py \
		tests/test_io_count.py::test_external_plan_measured_nio_matches_replay -q; \
	elif [ $$rc -eq 3 ]; then \
		echo "uring-lane SKIPPED: no io_uring or O_DIRECT here (reason above)"; \
	else exit $$rc; fi
