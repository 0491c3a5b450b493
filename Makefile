# Quality gates, mirroring the reference's Makefile:102-174 + ADR-002
# (unit tests w/ race detector -> pytest; golangci-lint -> tools/qa.py
# lint; gocyclo -over N -> tools/qa.py cyclo; coverage >= 80% ->
# tools/qa.py coverage on sys.monitoring). No third-party QA tools are
# baked into this image, so the gates are first-party (tools/qa.py).

PY ?= python
SHELL := /bin/bash           # pipefail in the test target

.PHONY: all check lint cyclo test test-asan coverage native clean hooks

all: check

check: lint cyclo test

lint:
	$(PY) tools/qa.py lint

cyclo:
	$(PY) tools/qa.py cyclo --over 12

# --tb=long is unconditional via pyproject addopts; keep the log so a
# flake's first occurrence is diagnosable (docs/qa_report.md)
test:
	set -o pipefail; $(PY) -m pytest tests/ -x -q 2>&1 | tee pytest.log

coverage:
	$(PY) tools/qa.py coverage --fail-under 80

native:
	$(MAKE) -C native

# ASAN gate for the native boundary (the reference runs its unit tests
# with the Go race detector on every invocation, Makefile:105; the C
# extension's refcount/lifetime discipline gets the equivalent here).
# LD_PRELOAD because the python binary itself is not ASAN-built;
# detect_leaks=0 because CPython intentionally leaks at interpreter
# exit and the interceptor would drown real findings in that noise.
# libstdc++ is preloaded alongside libasan: python itself links no C++
# runtime, so at preload-init dlsym(RTLD_NEXT, "__cxa_throw") finds
# nothing and the interceptor CHECK-fails the first time a dlopen'd
# C++ library (jaxlib) throws. Loading libstdc++ up front fixes the
# symbol resolution order.
ASAN_LIB = $(shell $(CXX) -print-file-name=libasan.so)
STDCXX_LIB = $(shell $(CXX) -print-file-name=libstdc++.so.6)
test-asan:
	$(MAKE) -C native asan
	# preflight: the gate must FAIL, not silently skip, if the
	# instrumented extensions don't load under the ASAN runtime
	LD_PRELOAD="$(ASAN_LIB) $(STDCXX_LIB)" \
	ASAN_OPTIONS=detect_leaks=0:abort_on_error=1 \
	MAXMQ_NATIVE_DIR=$(CURDIR)/native/asan \
	$(PY) -c "from maxmq_tpu import native; \
	    assert native.available(), 'asan ctypes lib failed to load'; \
	    assert native.decode_module(build=False), 'asan decode ext failed to load'; \
	    assert native.sender_module(), 'asan sender ext failed to load'"
	LD_PRELOAD="$(ASAN_LIB) $(STDCXX_LIB)" \
	ASAN_OPTIONS=detect_leaks=0:abort_on_error=1 \
	MAXMQ_NATIVE_DIR=$(CURDIR)/native/asan \
	JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_sig_parity.py tests/test_churn_stress.py \
	    tests/test_native.py tests/test_refdecode.py \
	    tests/test_native_sender.py -x -q

hooks:
	chmod +x scripts/githooks/*
	git config core.hooksPath scripts/githooks
	@echo "git hooks installed (pre-commit: lint+cyclo; pre-push: make check)"

clean:
	rm -rf .qa_coverage.json $(shell find . -name __pycache__ -type d)
