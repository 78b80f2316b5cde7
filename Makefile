# Developer entry points.  `make tier1` is the CI gate (ROADMAP.md): it
# deselects the `slow` tests (pyproject.toml's addopts), which
# `make test-slow` runs.

PY ?= python

.PHONY: tier1 test-slow conformance solver-gates sharding-tests \
	chaos-tests bench bench-gemm bench-gemm-mesh bench-smoke \
	bench-accuracy bench-lu tune td-tune ozaki-tune

tier1:
	PYTHONPATH=src $(PY) -m pytest -x -q

# each slow test carries a timeout mark that gives it the time (conftest.py)
test-slow:
	PYTHONPATH=src $(PY) -m pytest -q -m slow

# cross-backend x cross-precision matrix vs the ref oracles (CI job);
# the solver-marked cells are deselected here — among the focused CI
# jobs they run only in solver-gates (tier1 runs every test that is not
# marked slow)
conformance:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "not solver" \
	tests/test_conformance.py tests/test_accuracy_gate.py

# tiered refinement solver + LAPACK-grade residual gates (CI job): every
# test carrying the `solver` marker — the exact-rational factorization
# gates, the pivot/TRSM property layer, the solver conformance axis
solver-gates:
	PYTHONPATH=src $(PY) -m pytest -x -q -m solver

# every sharding-marked test on a real (forced host-device) 4-device mesh:
# the SUMMA conformance axis runs its 1xN / Nx1 / 2x2 cells instead of
# skipping, plus the 2x2 batched+sharded acceptance subprocess (CI job)
sharding-tests:
	XLA_FLAGS="$(XLA_FLAGS) --xla_force_host_platform_device_count=4" \
	PYTHONPATH=src $(PY) -m pytest -x -q -m sharding

# deterministic fault-injection suite (CI's chaos job): every FaultPlan
# injection class — limb flip, NaN/Inf poison, cache corruption, SUMMA
# panel loss, mid-refinement kill, backend failure — must end in a typed
# hazard error or an oracle-conformant recovered result.  Forced host
# devices so the panel-loss cells run on a real 2x2 mesh; writes
# CHAOS_REPORT.json (the hazard-report artifact CI uploads)
chaos-tests:
	XLA_FLAGS="$(XLA_FLAGS) --xla_force_host_platform_device_count=4" \
	PYTHONPATH=src $(PY) -m pytest -x -q -m chaos

bench:
	PYTHONPATH=src $(PY) -m benchmarks.run

# emits BENCH_GEMM.json (perf trajectory artifact) via benchmarks/common.py
bench-gemm:
	PYTHONPATH=src $(PY) -m benchmarks.run bench_gemm

# SUMMA topology sweep (per-mesh GEMM rows in BENCH_GEMM.json); pair with
# forced host devices to fill every topology, as CI's sharding job does
bench-gemm-mesh:
	XLA_FLAGS="$(XLA_FLAGS) --xla_force_host_platform_device_count=4" \
	PYTHONPATH=src $(PY) -m benchmarks.run bench_gemm --mesh 1x1,1x2,2x1,2x2

# every backend x tier at small n, conformance-checked against the ref
# oracle — exits nonzero on a conformance failure (CI's bench-smoke job)
bench-smoke:
	BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run bench_gemm

# emits BENCH_ACCURACY.json (per-tier observed relative error on the
# exact-rational Hilbert case; the accuracy regression artifact)
bench-accuracy:
	PYTHONPATH=src $(PY) -m benchmarks.run bench_accuracy

# blocked LU + the refinement-ladder sweep; emits BENCH_LU.json (the
# factor-cheap / refine-at-target cost trajectory, uploaded by CI)
bench-lu:
	PYTHONPATH=src $(PY) -m benchmarks.run bench_lu

# warm the on-disk GEMM plan cache for the common shape buckets
tune:
	PYTHONPATH=src $(PY) -c "from repro.gemm import autotune; \
	[autotune(n, n, n) for n in (64, 128, 256)]; \
	[autotune(n, n, n, precision='qd') for n in (64, 128)]"

# warm the td (triple-word) buckets: the systolic tile and the fused
# Ozaki-slice kernel tune independently per limb count (cache schema v4)
td-tune:
	PYTHONPATH=src $(PY) -c "from repro.gemm import autotune; \
	[autotune(n, n, n, precision='td') for n in (64, 128)]; \
	[autotune(n, n, n, backend='ozaki-pallas', precision='td') \
	 for n in (32, 64)]"

# sweep block shapes x n_slices for the fused Ozaki-slice kernel and
# persist the winners (dd tier at common buckets, qd at the small ones)
ozaki-tune:
	PYTHONPATH=src $(PY) -c "from repro.gemm import autotune; \
	[autotune(n, n, n, backend='ozaki-pallas') for n in (32, 64, 128)]; \
	[autotune(n, n, n, backend='ozaki-pallas', precision='qd') \
	 for n in (32, 64)]"
