# netobserv_tpu build/test entry points (reference analog: the Go Makefile's
# compile / gen-bpf / gen-protobuf / test targets).

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all test test-cpu gen-protobuf native bpf verify-maps lint perftest bytecode-image \
        dryrun smoke smoke-chip clean

all: native gen-protobuf

test:
	$(PY) -m pytest tests/ -x -q

# explicit CPU-mesh run (tests force this themselves; here for symmetry)
test-cpu:
	$(CPU_ENV) $(PY) -m pytest tests/ -x -q

# the quickest proof that the system still starts on the chip
smoke-chip:
	$(PY) chip_smoke.py

gen-protobuf:
	protoc --python_out=netobserv_tpu/pb -I proto proto/flow.proto proto/packet.proto

# host-side native components (always buildable with g++)
native:
	$(PY) -c "from netobserv_tpu.datapath.flowpack import build_native; \
	          import sys; sys.exit(0 if build_native(force=True) else 1)"

# eBPF datapath object — needs clang with BPF target support
bpf:
	cmake -S netobserv_tpu/datapath/native -B netobserv_tpu/datapath/native/build \
	      -DDATAPATH_BPF=ON
	cmake --build netobserv_tpu/datapath/native/build

# consistency between the C map definitions and the canonical registry
verify-maps:
	$(PY) -m pytest tests/test_datapath.py -x -q

dryrun:
	$(CPU_ENV) $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# minimum end-to-end slice: synthetic datapath -> pipeline -> stdout flows,
# then one live alert raise→clear cycle against the real binary (zoo
# syn_flood pcap -> tpu-sketch -> alert engine -> /query/alerts HTTP —
# scripts/smoke_alerts.py)
smoke:
	DATAPATH=synthetic EXPORT=stdout CACHE_ACTIVE_TIMEOUT=300ms \
	  timeout 3 $(PY) -m netobserv_tpu | head -5 || true
	JAX_PLATFORMS=cpu $(PY) scripts/smoke_alerts.py

# federation e2e slice (~20s, non-gating CI artifact): two in-process
# agents stream delta frames over real gRPC into a local aggregator and
# the cluster-wide query surface answers merged top-K/frequency/cardinality
smoke-federation:
	JAX_PLATFORMS=cpu $(PY) scripts/smoke_federation.py

# federation RAINY-day slice (~30s, non-gating CI artifact): agents come
# up before the aggregator (cold-start catch-up), the aggregator restarts
# once mid-run restoring its checkpoint, a query poller asserts no torn
# snapshot — all with the delta-ingest fault point armed (every push eats
# an injected delay), so the retry/idempotency machinery is exercised live
smoke-federation-chaos:
	JAX_PLATFORMS=cpu FAULT_POINTS="federation.delta_ingest:delay:0.02" \
	  $(PY) scripts/smoke_federation.py --failure-path

# kernel capture-plane load rig: sendmmsg storm -> parity check (needs root)
perftest:
	$(PY) examples/performance/local_perftest.py --packets 1000000 --flows 256

# bpfman bytecode container (labels generated from the canonical sources)
bytecode-image:
	docker build -f Containerfile.bytecode \
	  --build-arg PROGRAMS="$$($(PY) scripts/gen_bytecode_labels.py programs)" \
	  --build-arg MAPS="$$($(PY) scripts/gen_bytecode_labels.py maps)" \
	  -t netobserv-tpu-bytecode .

clean:
	rm -rf netobserv_tpu/datapath/native/build
	find . -name __pycache__ -type d -exec rm -rf {} +

gen-docs:
	$(PY) scripts/gen_config_docs.py

# full accuracy sweep -> docs/accuracy.md (detection sweeps included)
accuracy:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) scripts/accuracy_sweep.py
