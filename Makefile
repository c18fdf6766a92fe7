# netobserv_tpu build/test entry points (reference analog: the Go Makefile's
# compile / gen-bpf / gen-protobuf / test / bench targets).

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all test test-cpu bench gen-protobuf native bpf verify-maps lint perftest bytecode-image \
        dryrun smoke smoke-chip clean

all: native gen-protobuf

test:
	$(PY) -m pytest tests/ -x -q

# explicit CPU-mesh run (tests force this themselves; here for symmetry)
test-cpu:
	$(CPU_ENV) $(PY) -m pytest tests/ -x -q

# needs a TPU (exits non-zero without one). The bench-* targets below ask
# for the CPU by name: they give counts, bytes and correctness, and their
# rates are the CPU's own — printed under cpu_* names, never a chip's
bench:
	$(PY) bench.py

bench-cpu:
	JAX_PLATFORMS=cpu $(PY) bench.py

# the quickest proof that the system still starts on the chip
smoke-chip:
	$(PY) chip_smoke.py

# host path only (~15s): pack/transfer/fold rates, pack-thread scaling,
# roll-stall — the per-PR CI artifact (no device ingest loop, no oracle)
bench-host:
	JAX_PLATFORMS=cpu $(PY) bench.py --host-only

# same run at 1% trace sampling: the flight-recorder overhead A/B
# (docs/observability.md "Overhead budget"; compare host_fold_ms_p50 /
# host_path_sustained against the bench-host artifact)
bench-host-traced:
	TRACE_SAMPLE=0.01 JAX_PLATFORMS=cpu $(PY) bench.py --host-only

# per-stage breakdown on the CPU (~60s): ingest ablations (signals/asym/
# fanout on/off), superbatch ladder 1x/2x/4x. The Pallas arms need a TPU
# and do not run here
bench-device:
	JAX_PLATFORMS=cpu $(PY) bench.py --device-only

# eviction-plane decode rates (~10s, jax-free path): columnar
# decode/merge/align vs the per-key idiom on synthetic multi-CPU drains —
# the per-PR CI artifact for the userspace eviction half
bench-evict:
	JAX_PLATFORMS=cpu $(PY) bench.py --evict-only

# fused one-call host pipeline (~10s, jax-free path): fp_drain_to_resident
# vs the python island chain on identical injected drains — per-stage
# drain/merge/join/pack split + GIL-interference probe — the non-gating
# CI artifact for the native eviction pipeline (docs/architecture.md
# "Eviction plane")
bench-native:
	JAX_PLATFORMS=cpu $(PY) bench.py --native-only

# persistent-slot top-K ablation (~60s, CPU-friendly): slot-table vs the
# legacy concat+re-score update — cost (CM-only arm attributes the
# table's share) and top-N recall vs exact truth at 10k/100k distinct
# keys — the non-gating CI artifact for the device-resident heavy-hitter
# plane (docs/tpu_sketch.md "Persistent-slot heavy-hitter plane")
bench-topk:
	JAX_PLATFORMS=cpu $(PY) bench.py --topk-only

# tiered counter planes (~60s, CPU-friendly): tiered-vs-wide resident
# sketch memory — batch-walk rate, per-table bytes (the sketch_memory
# block), tier occupancy/promotion counts, heavy-hitter recall@100 vs the
# exact oracle — the non-gating CI artifact for the self-adjusting sketch
# memory plane (docs/tpu_sketch.md "Tiered counter planes")
bench-tiered:
	JAX_PLATFORMS=cpu $(PY) bench.py --tiered-only

# multi-tenant stacked sketch plane (~2-4 min, CPU-friendly): the
# one-dispatch-folds-every-tenant amortization ladder (N=1/8/64 tenants,
# small per-tenant batches) vs N sequential single-tenant dispatches of
# the same rows, plus per-tenant top-K recall through the production
# router — the non-gating CI artifact for SKETCH_TENANTS
# (docs/architecture.md "Multi-tenant sketch planes")
bench-tenants:
	JAX_PLATFORMS=cpu $(PY) bench.py --tenants-only

# sketch warehouse (~60s, CPU-friendly): per-window write amplification,
# raw-vs-compacted segment bytes, range-merge rate per ladder k, range
# top-K recall vs the union oracle — the non-gating CI artifact for the
# archive plane (docs/architecture.md "Sketch warehouse")
bench-archive:
	JAX_PLATFORMS=cpu $(PY) bench.py --archive-only

# overload control plane (~15s): overdriven synthetic feed against a
# fault-slowed fold — sustained admitted rate, AIMD shed-factor
# trajectory, heavy-hitter recall under shed vs unshed — the per-PR CI
# artifact for the shedding seam (docs/architecture.md
# "Overload & backpressure")
bench-overload:
	JAX_PLATFORMS=cpu $(PY) bench.py --overload-only

# adversarial scenario zoo (~90s): every netobserv_tpu/scenarios pcap
# replayed through a full in-process agent and graded END TO END through
# the live /query/* routes — top-K recall, alarm fire/quiet directions,
# victim naming, HLL cardinality error, CM error-bar honesty — the
# per-PR CI artifact for detection QUALITY (docs/architecture.md
# "Query plane")
bench-scenarios:
	JAX_PLATFORMS=cpu $(PY) bench.py --scenarios

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

bench-micro:
	$(PY) benchmarks/micro_bench.py

gen-docs:
	$(PY) scripts/gen_config_docs.py

# full accuracy sweep -> docs/accuracy.md (detection sweeps included)
accuracy:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) scripts/accuracy_sweep.py

# host-path + per-stage profiles of whatever device JAX finds
profile:
	$(PY) benchmarks/host_path_profile.py
	$(PY) benchmarks/ingest_stage_profile.py
