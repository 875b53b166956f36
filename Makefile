# Courier-TPU — common entry points.
# PYTHONPATH covers src/ (the package).
PY      ?= python
PYPATH  := src

.PHONY: test test-fast lint ci clean-autotune

test:            ## full tier-1 suite (incl. slow markers)
	PYTHONPATH=$(PYPATH) $(PY) -m pytest -x -q

test-fast:       ## fast split (excludes @slow: subprocess/multi-device/soak tests)
	PYTHONPATH=$(PYPATH) $(PY) -m pytest -q -m "not slow"

lint:            ## concurrency/style lint over the package (repro.analysis.lint)
	PYTHONPATH=$(PYPATH) $(PY) -m repro.analysis lint src/repro

ci: test-fast lint  ## single CI entry point: fast tests, lint

clean-autotune:  ## drop the persistent block-size autotune cache
	PYTHONPATH=$(PYPATH) $(PY) -c "from repro.kernels.autotune import \
	default_cache; default_cache.clear(); print('cleared', default_cache.path)"
