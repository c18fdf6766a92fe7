#!/usr/bin/env python3
"""Two-node cluster e2e, locally: netns "nodes" + agents + Loki + LogQL.

The single-host fallback of the Kind tier (e2e/cluster/kind/): the same
assertion the reference makes against a real cluster — per-flow byte
accounting queried back from Loki via LogQL
(`e2e/basic/flow_test.go:62-126`) — over a two-"node" topology:

    nodeA netns ──veth── host (router + mock Loki) ──veth── nodeB netns

One agent runs INSIDE each node netns (kernel datapath on its own veth,
EXPORT=direct-flp with a `write loki` stage pushing to the host Loki).
Known traffic crosses nodeA -> nodeB; the harness then queries Loki for
BOTH nodes' flows and asserts endpoints, packet counts, and exact UDP byte
accounting. Needs root; used by tests/test_cluster_e2e.py and runnable
standalone.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

A_HOST, A_NODE = "cla0", "cla1"
B_HOST, B_NODE = "clb0", "clb1"
NS_A, NS_B = "clnodeA", "clnodeB"
A_IP, B_IP = "10.231.0.2", "10.231.1.2"
HOST_A_IP, HOST_B_IP = "10.231.0.1", "10.231.1.1"

FLP_CONFIG = """
pipeline: [{name: w}]
parameters:
  - name: w
    write:
      type: loki
      loki:
        url: http://%(host)s:%(port)d
        labels: [NodeName]
        staticLabels: {job: netobserv}
"""


def run(*cmd, check=True, **kw):
    return subprocess.run(cmd, check=check, capture_output=True, text=True,
                          **kw)


def ns_exec(ns, *cmd):
    return ["ip", "netns", "exec", ns, *cmd]


def setup_topology() -> None:
    teardown_topology()
    for host_if, node_if, ns, host_ip, node_ip in (
            (A_HOST, A_NODE, NS_A, HOST_A_IP, A_IP),
            (B_HOST, B_NODE, NS_B, HOST_B_IP, B_IP)):
        run("ip", "link", "add", host_if, "type", "veth", "peer", "name",
            node_if)
        run("ip", "netns", "add", ns)
        run("ip", "link", "set", node_if, "netns", ns)
        run("ip", "addr", "add", f"{host_ip}/24", "dev", host_if)
        run("ip", "link", "set", host_if, "up")
        run(*ns_exec(ns, "ip", "addr", "add", f"{node_ip}/24", "dev",
                     node_if))
        run(*ns_exec(ns, "ip", "link", "set", node_if, "up"))
        run(*ns_exec(ns, "ip", "link", "set", "lo", "up"))
        run(*ns_exec(ns, "ip", "route", "add", "default", "via", host_ip))
    # the host routes between the two node subnets
    with open("/proc/sys/net/ipv4/ip_forward", "w") as fh:
        fh.write("1")


def teardown_topology() -> None:
    for link in (A_HOST, B_HOST):
        subprocess.run(["ip", "link", "del", link], capture_output=True)
    for ns in (NS_A, NS_B):
        subprocess.run(["ip", "netns", "del", ns], capture_output=True)


def start_agent(ns: str, node_if: str, node_name: str, loki_port: int,
                direction: str):
    env = dict(os.environ)
    env.update({
        "EXPORT": "direct-flp",
        "FLP_CONFIG": FLP_CONFIG % {"host": HOST_A_IP if ns == NS_A
                                    else HOST_B_IP, "port": loki_port},
        "INTERFACES": node_if,
        "DIRECTION": direction,
        "CACHE_ACTIVE_TIMEOUT": "300ms",
        "AGENT_IP": A_IP if ns == NS_A else B_IP,
        "NO_PROXY": "*",  # urllib must dial the veth directly
    })
    # NodeName rides a staticLabel-like env? the FLP map carries AgentIP;
    # tag the stream by node via staticLabels instead
    env["FLP_CONFIG"] = env["FLP_CONFIG"].replace(
        "staticLabels: {job: netobserv}",
        "staticLabels: {job: netobserv, node: %s}" % node_name)
    # `ip netns exec` unshares the MOUNT namespace per invocation, so the
    # bpffs mount (program pinning) must happen inside the agent's own exec
    return subprocess.Popen(
        ns_exec(ns, "sh", "-c",
                "mount -t bpf bpf /sys/fs/bpf 2>/dev/null; "
                f"exec {sys.executable} -m netobserv_tpu"),
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True, cwd=os.path.join(os.path.dirname(__file__), "..", ".."))


def logql(port: int, query: str) -> list[dict]:
    url = (f"http://127.0.0.1:{port}/loki/api/v1/query_range?query="
           + urllib.request.quote(query))
    with urllib.request.urlopen(url, timeout=5) as resp:
        data = json.load(resp)
    out = []
    for stream in data["data"]["result"]:
        for _ts, line in stream["values"]:
            out.append(json.loads(line))
    return out


def main() -> dict:
    from e2e.cluster.mock_loki import serve

    srv, port, _store = serve(0)
    setup_topology()
    agents = []
    try:
        agents.append(start_agent(NS_A, A_NODE, "nodeA", port, "egress"))
        agents.append(start_agent(NS_B, B_NODE, "nodeB", port, "ingress"))
        def send(n: int, src_port: int, dst_port: int, payload: int) -> None:
            sender = subprocess.run(ns_exec(NS_A, sys.executable, "-c", (
                "import socket, time\n"
                "s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)\n"
                f"s.bind(('{A_IP}', {src_port}))\n"
                f"for _ in range({n}):\n"
                f"    s.sendto(b'x' * {payload}, ('{B_IP}', {dst_port}))\n"
                "    time.sleep(0.05)\n")),
                capture_output=True, text=True)
            assert sender.returncode == 0, sender.stderr

        def totals(node: str, dst_port: int = 7777) -> tuple[int, int]:
            hits = logql(
                port, f'{{job="netobserv",node="{node}"}} | json '
                      f'| SrcAddr="{A_IP}" | DstAddr="{B_IP}" '
                      f'| DstPort={dst_port}')
            return (sum(int(h.get("Packets", 0)) for h in hits),
                    sum(int(h.get("Bytes", 0)) for h in hits))

        # attach + first eviction timer: proven, not slept for — probe
        # datagrams on another port until BOTH agents account one (a fixed
        # 4 s sleep lost the first measured datagrams whenever the machine
        # was busy: "nodeA packets 8 != 9")
        ready = time.time() + 60
        while True:
            for p in agents:
                assert p.poll() is None, \
                    f"agent died: {p.stderr.read()[-2000:]}"
            send(1, 47001, 7776, 10)
            time.sleep(0.5)
            if totals("nodeA", 7776)[0] and totals("nodeB", 7776)[0]:
                break
            assert time.time() < ready, "agents did not attach within 60s"

        # known traffic: 9 UDP datagrams, 100B payload, nodeA -> nodeB
        n_pkts, payload = 9, 100
        send(n_pkts, 47000, 7777, payload)

        # flows evict on the 300ms timer, so one logical flow surfaces as a
        # few records; the per-flow accounting assertion sums them (the
        # reference queries Loki the same way and aggregates)
        expected_bytes = n_pkts * (payload + 8 + 20 + 14)  # L2 frame bytes

        deadline = time.time() + 20
        sent = recv = (0, 0)
        while time.time() < deadline:
            sent, recv = totals("nodeA"), totals("nodeB")
            if sent[0] >= n_pkts and recv[0] >= n_pkts:
                break
            time.sleep(0.5)
        # the reference's bar: per-flow byte/packet accounting via LogQL,
        # from BOTH nodes' agents
        assert sent[0] == n_pkts, f"nodeA packets {sent[0]} != {n_pkts}"
        assert recv[0] == n_pkts, f"nodeB packets {recv[0]} != {n_pkts}"
        assert sent[1] == expected_bytes, \
            f"nodeA bytes {sent[1]} != {expected_bytes}"
        assert recv[1] == expected_bytes, \
            f"nodeB bytes {recv[1]} != {expected_bytes}"
        out = {"sent_flow": {"Packets": sent[0], "Bytes": sent[1]},
               "recv_flow": {"Packets": recv[0], "Bytes": recv[1]},
               "expected_bytes": expected_bytes}
        print(json.dumps(out))
        return out
    finally:
        for p in agents:
            p.terminate()
        for p in agents:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        teardown_topology()
        srv.shutdown()


if __name__ == "__main__":
    if os.geteuid() != 0:
        sys.exit("needs root (netns + CAP_BPF)")
    main()
