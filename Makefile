# Repo CI entry points.  Multi-device semantics run on simulated host CPU
# devices: the pytest main process stays single-device (see
# src/repro/launch/dryrun.py's device-count note) and the multi-device
# checks spawn their own 8-device subprocesses via testing/subproc.py;
# targets that exercise the mesh directly export the XLA flag themselves.

PY       ?= python
MP8       = XLA_FLAGS=--xla_force_host_platform_device_count=8
PYPATH    = PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}

.PHONY: test test-fast bench-smoke bench ckpt-smoke serve-smoke \
        serve-load-smoke moe-smoke ring-smoke fault-smoke kernel-smoke \
        obs-smoke tune-smoke

# tier-1 verify (ROADMAP.md): full suite, stop on first failure
test:
	$(PYPATH) $(PY) -m pytest -x -q

# skip the slow multi-device subprocess groups
test-fast:
	$(PYPATH) $(PY) -m pytest -x -q -m "not slow"

# checkpoint smoke: per-shard fp32 + INT8 save -> ELASTIC restore
# (world 8 -> 4 -> 2) in an 8-device subprocess (testing/subproc.py)
ckpt-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_state_elastic_restore', \
	            'check_state_quantized_roundtrip'], n_devices=8, \
	           timeout=1200); \
	print('ckpt smoke OK: per-shard save -> elastic restore verified')"

# serving smoke: continuous-batching engine end-to-end on a 4-device CPU
# mesh — 6 requests with mixed prompt lengths over 4 slots (recycling),
# INT8 per-shard checkpoint boot, greedy output checked bit-identical to
# the raw single-request prefill+decode path (testing/subproc.py)
serve-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_serve_engine_continuous_batching'], n_devices=4, \
	           timeout=1200); \
	print('serve smoke OK: continuous batching == per-request decode')"

# paged-serving load smoke (serve/kv_pool.py paged pool, DESIGN.md §10):
# the paged engine booted from an INT8 per-shard checkpoint must emit
# token streams bit-identical to the slab engine on 4- AND 8-device
# meshes (prefix cache hitting, pool fully drained after), the
# speculative self-draft path must stay token-identical with > 1
# accepted token per verify, then the multi-tenant trace bench runs its
# admission / prefix-TTFT / acceptance gates against the committed
# BENCH_serve.json structural snapshot
serve-load-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_serve_engine_paged'], n_devices=4, timeout=1200); \
	run_checks(['check_serve_engine_paged', \
	            'check_serve_engine_speculative'], n_devices=8, \
	           timeout=1800); \
	print('serve load smoke OK: paged == slab at 4/8 dev, speculative '\
	      'token-identical with >1 accepted/verify')"
	$(PYPATH) $(PY) -m benchmarks.serve_bench --smoke

# MoE overlap smoke: tiny deepseek-style MoE stack (shared + routed
# experts, chunked) with prefetch=1 — the layer-scan shared gathers and
# the nested expert-chunk gathers/reduces must be schedulable under
# compute (overlap_fraction > 0.5 from compiled HLO; 0.0 synchronous)
moe-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_moe_prefetch_overlap_fraction'], n_devices=8, \
	           timeout=1200); \
	print('moe smoke OK: chunk/layer MoE schedule overlap verified from HLO')"

# prefetch-ring smoke: 8-dev depth-2 dense + MoE overlap check from
# compiled HLO — structural overlap_fraction at depth 2 must be no lower
# than the depth-1 measurement, the depth-credited (effective) overlap
# strictly higher, and the MoE nested-remat expert re-gather no longer
# exposed (no gather-only loop)
ring-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_ring_overlap_depth'], n_devices=8, timeout=2400); \
	print('ring smoke OK: depth-2 ring beats depth-1 on dense + MoE')"

# elastic fault-tolerance smoke (train/elastic.py + testing/faults.py):
# async writer overlap, worker death -> bit-exact resume, transient-write
# retries, live 8->4->8 in-memory resharding, quarantine-and-fall-back on
# corrupt checkpoints, and REAL SIGKILL/SIGTERM subprocess scenarios
# (crash mid-write leaves only unselectable debris; graceful drain)
fault-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_elastic_async_overlap', \
	            'check_elastic_kill_resume', \
	            'check_elastic_flaky_io_retry'], n_devices=8, \
	           timeout=1800); \
	run_checks(['check_elastic_live_reshard', \
	            'check_elastic_corrupt_fallback'], n_devices=8, \
	           timeout=1800); \
	run_checks(['check_elastic_crash_during_write', \
	            'check_elastic_sigterm_grace'], n_devices=8, \
	           timeout=1800); \
	print('fault smoke OK: async ckpt overlap, bit-exact resume, live '\
	      'reshard, corrupt fallback, real-signal crash/drain verified')"

# kernel-backend smoke (kernels/ops.py dispatch seam, DESIGN.md §7):
# interpret-mode parity suite for every Pallas kernel body (quant /
# dequant / fused reorder+quant / dequant-reduce-requant / INT8
# dequant-GEMM vs the pure-jnp oracles), then the schedule- and
# serve-level composition checks with the backend forced to interpret
# (depth sweep bit-exact, fused INT8 serving head == staged head,
# xla-vs-interpret training bit-identity), then a kernel_bench smoke run
kernel-smoke:
	$(PYPATH) $(PY) -m pytest -x -q tests/test_kernels.py \
		-k "not 8dev"
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_kernel_backend_depth_sweep', \
	            'check_qwz_gemm_head_matches_staged', \
	            'check_kernel_backend_train_bitexact'], n_devices=8, \
	           timeout=2400); \
	run_checks(['check_serve_engine_continuous_batching'], n_devices=8, \
	           timeout=1800, \
	           extra_env={'REPRO_KERNEL_BACKEND': 'interpret'}); \
	print('kernel smoke OK: interpret-mode parity + kernel-backed '\
	      'schedule/serve bit-exactness verified')"
	$(PYPATH) $(PY) -m benchmarks.kernel_bench --smoke

# observability smoke (obs/, DESIGN.md §8): measured-vs-projected comm
# crosscheck per collective label (dense + MoE, ring depths 0/1/2),
# telemetry-under-failure jsonl replay (kill/restart -> totals equal the
# uninterrupted oracle), and the runtime gate on a REAL 8-dev train run
# (comm bytes within 1% of the analytic projection, telemetry-disabled
# overhead < 2%)
obs-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_obs_comm_crosscheck'], n_devices=8, timeout=1800); \
	run_checks(['check_obs_comm_crosscheck_moe'], n_devices=8, \
	           timeout=1800); \
	run_checks(['check_obs_telemetry_failure_replay', \
	            'check_obs_runtime_gate'], n_devices=8, timeout=1800); \
	print('obs smoke OK: comm counters match analytics, replay survives '\
	      'kill/restart, runtime gate passes')"

# tuner smoke (repro/tune, DESIGN.md §9): the (k+1)-ring HBM ledger vs
# the MEASURED live gathered-buffer counts in the traced train step for
# prefetch 0..3, the --tune=static boot path (build_everything resolves
# to the same frozen policy as a direct resolve call and trains), then
# the static resolution sweep checked against the committed
# BENCH_tuner.json snapshot (deterministic by the static-profile
# contract)
tune-smoke:
	$(PYPATH) $(PY) -c "\
	from repro.testing.subproc import run_checks; \
	run_checks(['check_tune_ledger_live_buffers', \
	            'check_tune_static_resolve_boot'], n_devices=8, \
	           timeout=1800); \
	print('tune smoke OK: ledger matches live ring buffers at k=0..3, '\
	      'static boot path resolves deterministically')"
	$(PYPATH) $(PY) -m benchmarks.tuner_report

# overlap benchmark + suite smoke in one command: verifies the prefetched
# schedule from compiled HLO on the 8-device CPU mesh, then prints the
# overlap-aware throughput projection (paper Table 2 analogue)
bench-smoke:
	$(MP8) $(PYPATH) $(PY) -c "\
	from repro.testing.checks import check_prefetch_overlap_fraction; \
	check_prefetch_overlap_fraction(); \
	print('overlap verified: prefetch=1 overlappable, prefetch=0 exposed')"
	$(PYPATH) $(PY) benchmarks/throughput_model.py

# full benchmark battery (paper tables/figures)
bench:
	$(PYPATH) $(PY) -m benchmarks.run
