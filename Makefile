# Dual-mode test targets (reference: madsim's Makefile drives
# `cargo test` and `RUSTFLAGS="--cfg madsim" cargo test`; here the modes
# are sim [default], real sockets, and the TPU engine CLI).

PY ?= python

.PHONY: test stest rtest check lint lint-fast rpc-bench explore examples audit

# full suite (host engine + TPU engine on a hermetic 8-dev CPU mesh)
test:
	$(PY) -m pytest tests/ -x -q

# sim-only subset (fast; no jax)
stest:
	$(PY) -m pytest tests/ -x -q --ignore=tests/test_engine.py \
		--ignore=tests/test_pallas.py --ignore=tests/test_soak.py \
		--ignore=tests/test_native.py

# real-socket mode + genuine-wire passthrough suites
rtest:
	$(PY) -m pytest tests/test_real_mode.py tests/test_grpc_real.py \
		tests/test_etcd_real.py tests/test_s3_real.py \
		tests/test_kafka_real.py -x -q

# corpus digest-trail audit (first-divergent-checkpoint bisection)
audit:
	$(PY) -m madsim_tpu audit

# determinism self-checks (host harness + engine)
check:
	MADSIM_TEST_NUM=8 MADSIM_TEST_CHECK_DETERMINISM=1 \
		$(PY) -m pytest tests/test_rand.py -x -q
	$(PY) -m madsim_tpu check --machine raft --seeds 32

# determinism & contract static analysis (pre-commit friendly exits)
lint:
	$(PY) -m madsim_tpu lint madsim_tpu/

# cached re-lint for the edit loop / pre-commit hook: --changed scopes
# the run to git-dirty files + their reverse import-graph dependents
# (a no-change run exits immediately; the T/S whole-program walks only
# re-run when the step-path zone moved), --cache replays unchanged
# files from .madsim-lint-cache/; --no-import-check keeps it jax-free
# — CI runs everything cold and unscoped
lint-fast:
	$(PY) -m madsim_tpu lint madsim_tpu/ --cache --no-import-check --changed

# reference-criterion-style microbenches
rpc-bench:
	$(PY) benches/rpc_bench.py

explore:
	$(PY) -m madsim_tpu explore --machine raft --seeds 4096

examples:
	$(PY) examples/raft_host.py 10
	$(PY) examples/chaos_pipeline.py 42
	$(PY) examples/delay_hunt.py
