# Developer surface, mirroring the reference's Makefile targets
# (test / clustertests) and its CI matrix (-race runs and the
# SHARD_WIDTH build-tag job, .circleci/config.yml:52-64) adapted to
# this build: the paranoia gate is our sanitizer tier and the shard
# width is env-configurable rather than a build tag.  The benchmark is
# not a target here: BENCHMARK.json names its command (perfbench/run.py)
# and it measures only on the chip.

PY ?= python

.PHONY: test test-paranoia test-shard22 test-matrix analyze typecheck validate-tpu chip-smoke soak soak-spmd check doccheck doccheck-fill native clean

test:
	$(PY) -m pytest tests/ -x -q

# pilosa-lint: the six project-invariant analysis passes over the
# package (tools/analyze/) — exit 1 on any unsuppressed finding.
# tests/test_analyze.py pins the committed tree at zero.
analyze:
	$(PY) -m tools.analyze pilosa_tpu

# mypy over the strict scope (mypy.ini; ops/tape.py, ops/expr.py,
# runtime/resultcache.py).  Gates gracefully when mypy is absent.
typecheck:
	$(PY) tools/typecheck.py

# build all four C++ fast paths from the committed .cpp files (they
# also self-build lazily); a build/ left by another image is dropped
# first, and a library that does not build fails the target
native:
	rm -rf pilosa_tpu/native/build
	$(PY) -c "import sys; from pilosa_tpu import native_loader; \
	st = native_loader.status(); \
	[print(f'{n:<14}', 'native' if v['loaded'] else 'FAILED: ' + str(v['error'])) for n, v in st.items()]; \
	sys.exit(0 if all(v['loaded'] for v in st.values()) else 1)"

# sanitizer tier: every fragment mutation re-validates invariants
test-paranoia:
	PILOSA_TPU_PARANOIA=1 $(PY) -m pytest tests/ -x -q

# shard-width independence (reference SHARD_WIDTH=22 matrix job)
test-shard22:
	PILOSA_TPU_SHARD_WIDTH_EXP=22 $(PY) -m pytest tests/ -x -q

test-matrix: analyze typecheck test test-paranoia test-shard22

# executable documentation: verify every doc example against a live
# server; doccheck-fill rewrites the response blocks from actual
# results (the authoring loop)
doccheck:
	$(PY) tools/doccheck.py docs/query-language.md docs/getting-started.md

doccheck-fill:
	$(PY) tools/doccheck.py --fill docs/query-language.md docs/getting-started.md

# on-chip Pallas validation (fails without a TPU)
# chip-smoke: the whole served path on the chip, validator included
validate-tpu:
	$(PY) benchmarks/validate_tpu.py

chip-smoke:
	$(PY) chip_smoke.py

# long randomized differential soak (usage: make soak SOAK_SECONDS=1500)
SOAK_SECONDS ?= 300
soak:
	$(PY) tools/soak.py --seconds $(SOAK_SECONDS)

# multi-process collective-plane soak (usage: make soak-spmd
# SOAK_SECONDS=600 SOAK_PROCS=2)
SOAK_PROCS ?= 2
soak-spmd:
	$(PY) tools/soak_spmd.py --seconds $(SOAK_SECONDS) --procs $(SOAK_PROCS)

# offline data-dir integrity (usage: make check DIR=/path/to/data)
check:
	$(PY) -m pilosa_tpu check $(DIR)

clean:
	rm -rf pilosa_tpu/native/build
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
