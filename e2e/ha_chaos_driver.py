"""HA chaos e2e: kill -9 the apiserver and the active scheduler mid-wave,
recover from the WAL, fail over the Lease, and lose zero work
(CI job ha-chaos-e2e).

Three real processes beyond this driver: an apiserver running on the
durable WAL+snapshot backend (``APISERVER_WAL_DIR``,
``apiserver/wal.py``) and TWO scheduler replicas under leader election
(``ENABLE_LEADER_ELECTION=true`` — ``runtime/bootstrap.py`` wires the
Lease through the apiserver). The chaos monkey's process-level injectors
(``kill9_apiserver`` / ``kill9_scheduler``, ``runtime/chaos.py``) deliver
real SIGKILLs — no shutdown hook runs, the WAL's fsynced prefix is all
that survives. The storyline:

1. submit the first half of a gang wave; wait until bindings are landing,
2. kill -9 the apiserver mid-wave; restart it against the SAME WAL dir and
   assert recovery: every object back, the RV counter strictly monotonic
   (``/healthz`` exposes it; new writes must mint fresh RVs, never reuse),
   timed as ``recovery_replay_seconds``,
3. assert the ACTIVE scheduler's informers healed across the restart —
   watch reconnect + paginated relist from their durable RVs
   (``informer_watch_reconnects_total`` / ``informer_relists_total`` on
   its /metrics) — riding the client's transient-connection retry,
4. kill -9 the active scheduler; the standby must take over the Lease
   (``leader_election_state{role="scheduler"}`` flips on its /metrics),
   rebuild its ledger from recovered pods, and bind the REST of the wave
   (submitted after the kill): ``failover_to_bind_s`` is kill → last bind,
5. assert zero dropped work (every gang of both halves fully bound) and
   ledger consistency (no node over chip capacity, gangs unsplit where
   sized to fit) from the recovered state.

Exit 0 on success, 1 with a JSON failure report. CPU-only, seconds-scale.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

SEED = 16
#: 16 nodes x 4 chips covers the whole wave's 56-chip demand with packing
#: headroom — zero-dropped-work needs every gang to eventually FIT
NODES = int(os.environ.get("HA_NODES", "16"))
GANGS = int(os.environ.get("HA_GANGS", "6"))
MAX_GANG = int(os.environ.get("HA_MAX_GANG", "4"))
#: fast lease so standby takeover (bounded by lease_duration) stays quick
LEASE_DURATION = os.environ.get("HA_LEASE_DURATION", "2.0")
LEASE_RENEW = os.environ.get("HA_LEASE_RENEW", "0.25")
#: small snapshot interval: the restart must exercise snapshot+tail replay
#: AND push the journal floor past stale informer RVs → 410 → relist
SNAPSHOT_EVERY = os.environ.get("HA_WAL_SNAPSHOT_EVERY", "10")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def _metric_value(text: str, name: str, **labels) -> float:
    """Sum of series for ``name`` whose label set includes ``labels``."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest[:1] not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _poll(fn, timeout: float = 30.0, interval: float = 0.1,
          desc: str = "condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        value = fn()
        if value:
            return value
        time.sleep(interval)
    raise AssertionError(f"timed out after {timeout}s waiting for {desc}")


def _healthz_rv(base: str) -> int:
    return int(json.loads(_get(f"{base}/healthz"))["resourceVersion"])


def _scrape(ops: str) -> str:
    try:
        return _get(f"{ops}/metrics", timeout=2.0).decode()
    except (urllib.error.URLError, OSError):
        return ""


def run() -> dict:
    from kubeflow_tpu.apiserver.remote import RemoteStore
    from kubeflow_tpu.runtime.chaos import ChaosMonkey, ChaosSchedule, Fault
    from kubeflow_tpu.scale.loadgen import LoadGenerator
    from kubeflow_tpu.scale.topology import synth_gangs, synthesize
    from kubeflow_tpu.scheduler.gang import POD_GROUP_LABEL
    from kubeflow_tpu.tpu.topology import RESOURCE_TPU

    api_port = _free_port()
    base = f"http://127.0.0.1:{api_port}"
    wal_dir = tempfile.mkdtemp(prefix="ha-chaos-wal-")
    # control-plane roles need no chip, and a chip belongs to one process
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    api_env = {**child_env, "API_PORT": str(api_port),
               "APISERVER_WAL_DIR": wal_dir,
               "APISERVER_WAL_SNAPSHOT_EVERY": SNAPSHOT_EVERY}
    procs: dict = {}
    sched_ops: dict = {}

    def spawn_apiserver() -> None:
        procs["apiserver"] = subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.apiserver"], env=api_env)

    def spawn_scheduler(key: str) -> None:
        sched_ops[key] = f"http://127.0.0.1:{_free_port()}"
        procs[key] = subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.scheduler.core"],
            env={**child_env, "APISERVER_URL": base,
                 "METRICS_PORT": sched_ops[key].rsplit(":", 1)[1],
                 "ENABLE_LEADER_ELECTION": "true",
                 "LEASE_DURATION": LEASE_DURATION,
                 "LEASE_RENEW_INTERVAL": LEASE_RENEW})

    def leading(key: str) -> bool:
        return _metric_value(_scrape(sched_ops[key]),
                             "leader_election_state", role="scheduler") >= 1.0

    def active_scheduler() -> str:
        for key in ("scheduler-a", "scheduler-b"):
            if procs[key].poll() is None and leading(key):
                return key
        return ""

    try:
        spawn_apiserver()
        RemoteStore(base).wait_ready(timeout=60.0)
        spawn_scheduler("scheduler-a")
        spawn_scheduler("scheduler-b")
        # the monkey resolves procs lazily so restarted processes are seen
        monkey = ChaosMonkey(None, ChaosSchedule([]),
                             procs={"apiserver": lambda: procs["apiserver"],
                                    "scheduler-a": lambda: procs["scheduler-a"],
                                    "scheduler-b": lambda: procs["scheduler-b"]})
        active = _poll(active_scheduler, timeout=60.0, interval=0.25,
                       desc="one scheduler to win the Lease")
        standby = "scheduler-b" if active == "scheduler-a" else "scheduler-a"

        # -- 1. first half of the wave lands while everything is healthy ----
        topo = synthesize(NODES, seed=SEED)
        gen = LoadGenerator(base, topo, seed=SEED)
        assert gen.register_nodes() == topo.total_nodes
        shapes = synth_gangs(topo, GANGS, seed=SEED, prefix="ha",
                             max_size=MAX_GANG)
        first, second = shapes[:len(shapes) // 2], shapes[len(shapes) // 2:]
        gen.gang_wave(first)
        _poll(lambda: gen.bound_gangs(), timeout=60.0,
              desc="first bindings before the kill")

        # -- 2. kill -9 the apiserver mid-wave; recover from the WAL --------
        # Wait for a snapshot covering every pod write so far: on recovery
        # the journal floor is the newest snapshot's rv, so the scheduler's
        # pod informer (resume rv < floor) deterministically gets 410 and
        # must heal via the paginated relist. Lease renewals (~4 writes/s)
        # push the WAL over the snapshot threshold on their own.
        rv_mark = _healthz_rv(base)

        def _newest_snapshot_rv() -> int:
            rvs = [int(n[len("snapshot_"):-len(".bin")])
                   for n in os.listdir(wal_dir)
                   if n.startswith("snapshot_") and n.endswith(".bin")]
            return max(rvs, default=0)

        _poll(lambda: _newest_snapshot_rv() >= rv_mark, timeout=60.0,
              interval=0.25, desc="a snapshot past the wave's last write")
        rv_before = _healthz_rv(base)
        heal_base = {
            "reconnects": _metric_value(_scrape(sched_ops[active]),
                                        "informer_watch_reconnects_total"),
            "relists": _metric_value(_scrape(sched_ops[active]),
                                     "informer_relists_total"),
        }
        monkey.inject(Fault(at=0.0, kind="kill9_apiserver"))
        assert procs["apiserver"].poll() is not None, "SIGKILL must be fatal"
        t0 = time.monotonic()
        spawn_apiserver()
        RemoteStore(base).wait_ready(timeout=60.0)
        recovery_replay_seconds = time.monotonic() - t0
        rv_after = _healthz_rv(base)
        assert rv_after >= rv_before, (
            f"recovered RV counter went backwards: {rv_after} < {rv_before}")
        # a fresh write must mint an RV strictly above everything pre-crash
        marker = json.dumps({"apiVersion": "v1", "kind": "ConfigMap",
                             "metadata": {"name": "ha-rv-probe",
                                          "namespace": "default"}}).encode()
        req = urllib.request.Request(
            f"{base}/api/v1/namespaces/default/configmaps", data=marker,
            headers={"content-type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=10.0) as resp:
            minted = int(json.loads(resp.read())["metadata"]["resourceVersion"])
        assert minted > rv_before, (minted, rv_before)
        # zero dropped writes: every pre-crash pod recovered from the WAL
        recovered = gen._list_pods()
        want_pods = sum(s.size for s in first)
        got = [p for p in recovered
               if (p["metadata"].get("labels") or {}).get(POD_GROUP_LABEL)]
        assert len(got) == want_pods, (
            f"WAL recovery dropped pods: {len(got)}/{want_pods}")

        # -- 3. the active scheduler's informers heal across the restart ----
        def informers_healed():
            text = _scrape(sched_ops[active])
            return (_metric_value(text, "informer_watch_reconnects_total")
                    > heal_base["reconnects"]
                    and _metric_value(text, "informer_relists_total")
                    > heal_base["relists"])

        _poll(informers_healed, timeout=60.0, interval=0.25,
              desc="active scheduler informer reconnect+relist")

        # -- 4. kill -9 the active scheduler; the standby finishes the wave --
        monkey.inject(Fault(at=0.0, kind="kill9_scheduler", target=active))
        assert procs[active].poll() is not None, "SIGKILL must be fatal"
        t_failover = time.monotonic()
        _poll(lambda: leading(standby), timeout=60.0, interval=0.1,
              desc="standby scheduler to take over the Lease")
        gen.gang_wave(second)
        gen.wait_gangs_bound([s.name for s in shapes], timeout_s=120.0)
        failover_to_bind_s = time.monotonic() - t_failover

        # -- 5. zero dropped work + consistent ledger from recovered pods ---
        pods = gen._list_pods()
        by_gang: dict = {}
        used: dict = {}
        for pod in pods:
            gang = (pod["metadata"].get("labels") or {}).get(POD_GROUP_LABEL)
            node = (pod.get("spec") or {}).get("nodeName")
            if not gang:
                continue
            assert node, f"unbound pod after recovery: {pod['metadata']['name']}"
            by_gang.setdefault(gang, []).append(pod)
            chips = int(pod["spec"]["containers"][0]["resources"]["limits"]
                        [RESOURCE_TPU])
            used[node] = used.get(node, 0) + chips
        for shape in shapes:
            assert len(by_gang.get(shape.name, [])) == shape.size, (
                f"gang {shape.name}: {len(by_gang.get(shape.name, []))}"
                f"/{shape.size} bound — dropped work")
        capacity = {n["metadata"]["name"]:
                    int(n["status"]["allocatable"][RESOURCE_TPU])
                    for n in json.loads(_get(f"{base}/api/v1/nodes"))["items"]}
        for node, chips in used.items():
            assert chips <= capacity[node], (
                f"ledger rebuilt inconsistently: node {node} over capacity "
                f"({chips} > {capacity[node]})")
        # the RV stream stayed strictly monotonic through crash + failover
        rv_final = _healthz_rv(base)
        assert rv_final > minted > rv_before

        return {
            "ok": True,
            "gangs_bound": len(shapes),
            "pods_bound": sum(s.size for s in shapes),
            "recovery_replay_seconds": round(recovery_replay_seconds, 3),
            "failover_to_bind_s": round(failover_to_bind_s, 3),
            "rv": {"before_kill": rv_before, "after_recovery": rv_after,
                   "final": rv_final},
            "active_then": active, "active_now": standby,
        }
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(wal_dir, ignore_errors=True)


def main() -> int:
    try:
        report = run()
    except AssertionError as e:
        print(json.dumps({"ok": False, "error": str(e)}))
        return 1
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
