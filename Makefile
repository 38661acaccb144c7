# Build/test/deploy targets mirroring the reference's kubebuilder Makefile
# surface (/root/reference/Makefile) where each has a meaning here.
IMG ?= ghcr.io/ollama-operator-tpu/tpu-runtime:v0.1.0
BACKEND ?= tpu
PY ?= python

.PHONY: all test test-fast lint lint-verbose kernel-interpret native bench \
        bench-smoke docker-build docker-build-cpu build-installer install \
        uninstall deploy undeploy kind-e2e clean

all: test build-installer

##@ Development

test:  ## full suite on the 8-device CPU mesh (conftest.py sets XLA flags)
	$(PY) -m pytest tests/ -q

test-fast:  ## operator + serving tiers only (no engine compiles)
	$(PY) -m pytest tests/test_operator_*.py tests/test_registry.py \
	  tests/test_modelfile.py tests/test_template.py -q

lint:  ## pyflakes (or py_compile) + the invariant linter (tools/invariant_lint)
	$(PY) -m pyflakes ollama_operator_tpu tests 2>/dev/null || \
	  $(PY) -m py_compile $$(git ls-files '*.py')
	$(PY) -m tools.invariant_lint --root .

lint-verbose:  ## invariant linter incl. suppressed findings + per-pass table
	$(PY) -m tools.invariant_lint --root . --verbose

kernel-interpret:  ## pallas kernels in interpret mode on CPU: fused paged A/B, int4 pool, device grammar
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pallas.py tests/test_paged.py \
	  tests/test_paged_fused.py tests/test_grammar_device.py -q

# (grammar otherwise builds lazily at the first format:"json" request —
# a latency spike)
native:  ## build the C++ dequant + grammar libraries
	mkdir -p native/build
	g++ -O3 -march=native -shared -fPIC \
	  -o native/build/libtpuop_dequant.so native/dequant.cpp
	g++ -O3 -std=c++17 -shared -fPIC \
	  -o native/build/libtpuop_grammar.so native/grammar.cpp

bench:  ## headline decode-throughput benchmark (one JSON line)
	$(PY) bench.py

# BENCH_XLA_CACHE=0: the CPU-backend persistent-cache deserialization
# path is unstable on some hosts (wrong tokens, then a native crash) —
# tiny smoke programs recompile in seconds anyway
bench-smoke:  ## seconds-scale CPU bench: engine + HTTP + mixed + prefix + overload + restart + coldstart + fused-paged + disagg arms
	JAX_PLATFORMS=cpu BENCH_HTTP=1 BENCH_MIXED_ARM=1 \
	  BENCH_PREFIX_ARM=1 BENCH_TIER_ARMS=1 \
	  BENCH_PAGED_ASYNC_ARM=1 BENCH_PAGED_FUSED_ARM=1 \
	  BENCH_OVERLOAD_ARM=1 BENCH_RESTART_ARM=1 BENCH_COLDSTART_ARM=1 \
	  BENCH_DISAGG_ARM=1 BENCH_ASSERT_DISAGG=1 \
	  BENCH_ASSERT_COLDSTART=1 BENCH_XLA_CACHE=0 \
	  BENCH_SLOTS=4 BENCH_STEPS=16 BENCH_SEQ=512 BENCH_PROMPT=16 \
	  BENCH_CAPTURE_LOG=0 $(PY) bench.py

##@ Build

docker-build:
	docker build --build-arg BACKEND=$(BACKEND) -t $(IMG) .

docker-build-cpu:
	docker build --build-arg BACKEND=cpu -t $(IMG) .

build-installer:  ## dist/install.yaml (single-file apply, ref Makefile:117)
	$(PY) hack/build_installer.py --image $(IMG)

##@ Deployment

install:  ## CRDs only
	kubectl apply -f config/crd/ollama.ayaka.io_models.yaml

uninstall:
	kubectl delete -f config/crd/ollama.ayaka.io_models.yaml

deploy: build-installer
	kubectl apply -f dist/install.yaml

undeploy:
	kubectl delete -f dist/install.yaml

kind-e2e:  ## CPU-backend image into a kind cluster (ref test-e2e analog)
	kind create cluster --config hack/kind-config.yaml || true
	$(MAKE) docker-build-cpu
	kind load docker-image $(IMG)
	$(MAKE) deploy
	kubectl apply -f config/samples/ollama_v1_model.yaml

clean:
	rm -rf native/build dist/install.yaml
