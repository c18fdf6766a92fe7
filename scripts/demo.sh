#!/bin/bash
# End-to-end demo battery (replayable evidence of the major paths).
# Requirements vary per section; each prints its own verdict and skips
# gracefully. Run from the repo root: bash scripts/demo.sh
set -u
cd "$(dirname "$0")/.."
PY=${PY:-python}

section() { echo; echo "=== $1"; }

setup_demo_net() {
  mountpoint -q /sys/fs/bpf || mount -t bpf bpf /sys/fs/bpf 2>/dev/null
  teardown_demo_net
  ip link add demo0 type veth peer name demo1 2>/dev/null
  ip netns add demons 2>/dev/null
  ip link set demo1 netns demons
  ip addr add 10.195.0.1/24 dev demo0 && ip link set demo0 up
  ip netns exec demons ip addr add 10.195.0.2/24 dev demo1
  ip netns exec demons ip link set demo1 up
  MAC=$(ip netns exec demons cat /sys/class/net/demo1/address)
  ip neigh replace 10.195.0.2 lladdr "$MAC" dev demo0 nud permanent
}

teardown_demo_net() {
  ip link del demo0 2>/dev/null
  ip netns del demons 2>/dev/null
  true
}

section "1. Synthetic traffic -> flow records (no privileges)"
DATAPATH=synthetic EXPORT=stdout CACHE_ACTIVE_TIMEOUT=300ms \
  timeout 3 $PY -m netobserv_tpu 2>/dev/null | head -2 || true

section "2. REAL kernel flow capture (root + CAP_BPF + tc)"
if [ "$(id -u)" = 0 ] && command -v tc >/dev/null && command -v ip >/dev/null; then
  setup_demo_net
  EXPORT=stdout INTERFACES=demo0 DIRECTION=egress CACHE_ACTIVE_TIMEOUT=300ms \
    timeout 6 $PY -m netobserv_tpu > /tmp/demo_flows.jsonl 2>/dev/null &
  sleep 3
  $PY - <<'PYEOF'
import socket
s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
for i in range(5):
    s.sendto(b"demo" * 20, ("10.195.0.2", 4242))
PYEOF
  wait
  teardown_demo_net
  grep 4242 /tmp/demo_flows.jsonl | head -1 \
    && echo "[ok] flows captured by the in-kernel program" \
    || echo "[!!] no flows captured"
else
  echo "skipped (needs root + iproute2)"
fi

section "2b. Embedded FLP pipeline: conntrack + service enrichment (root)"
if [ "$(id -u)" = 0 ] && command -v ip >/dev/null; then
  setup_demo_net
  timeout 8 ip netns exec demons $PY -c "
import socket
s=socket.socket();s.setsockopt(socket.SOL_SOCKET,socket.SO_REUSEADDR,1)
s.bind(('10.195.0.2',8080));s.listen(1)
c,_=s.accept();c.recv(100);c.sendall(b'r'*400);c.close()" &
  FLP_CONFIG='{"pipeline":[{"name":"n"},{"name":"ct","follows":"n"},{"name":"w","follows":"ct"}],
    "parameters":[
      {"name":"n","transform":{"type":"network","network":{"rules":[
        {"type":"add_service","add_service":{"input":"DstPort","output":"Service","protocol":"Proto"}}]}}},
      {"name":"ct","extract":{"type":"conntrack","conntrack":{
        "keyDefinition":{"fieldGroups":[{"name":"src","fields":["SrcAddr","SrcPort"]},
                                         {"name":"dst","fields":["DstAddr","DstPort"]},
                                         {"name":"common","fields":["Proto"]}],
                         "hash":{"fieldGroupRefs":["common"],"fieldGroupARef":"src","fieldGroupBRef":"dst"}},
        "outputRecordTypes":["endConnection"],
        "outputFields":[{"name":"Bytes","operation":"sum","splitAB":true},
                         {"name":"numFlowLogs","operation":"count"}],
        "scheduling":[{"endConnectionTimeout":"2s","terminatingTimeout":"200ms"}],
        "tcpFlags":{"fieldName":"Flags","detectEndConnection":true}}}},
      {"name":"w","write":{"type":"stdout"}}]}' \
  EXPORT=direct-flp INTERFACES=demo0 DIRECTION=both CACHE_ACTIVE_TIMEOUT=400ms \
    timeout 8 $PY -m netobserv_tpu > /tmp/demo_conn.jsonl 2>/dev/null &
  sleep 3
  $PY - <<'PYEOF'
import socket
c = socket.socket(); c.settimeout(4)
c.connect(("10.195.0.2", 8080))
c.sendall(b"q" * 80); c.recv(500); c.close()
PYEOF
  wait
  teardown_demo_net
  grep endConnection /tmp/demo_conn.jsonl | grep 8080 | head -1 \
    && echo "[ok] live TCP conversation stitched into one connection record" \
    || echo "[!!] no connection record"
else
  echo "skipped (needs root + iproute2)"
fi

section "3. TPU-sketch analytics (window reports, on the CPU)"
JAX_PLATFORMS=cpu DATAPATH=synthetic EXPORT=tpu-sketch SKETCH_WINDOW=3s \
  SKETCH_CM_WIDTH=16384 SKETCH_TOPK=64 CACHE_ACTIVE_TIMEOUT=300ms \
  timeout 10 $PY -m netobserv_tpu 2>/dev/null | head -1 || true

section "4. Multichip dry-run (8 virtual devices)"
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  timeout 200 $PY -c "import __graft_entry__ as g; g.dryrun_multichip(8)" || true

echo; echo "demo complete"
