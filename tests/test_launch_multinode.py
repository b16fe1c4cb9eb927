"""Multi-process launch -> KV rendezvous -> collective integration test
(VERDICT r2 item 6; reference model:
test/collective/test_communication_api_base.py:26,53,59 — every distributed
test runs real rank subprocesses that rendezvous and jointly execute work,
including simulated multi-node with nnode=2).

Two *launcher* OS processes (pods), each spawning 2 worker OS processes:
4 ranks across 2 pods rendezvous through the native C++ KV store
(csrc/kv_store.cpp) hosted by pod 0, then jointly verify:
  - the full PADDLE_TRAINER_* env contract,
  - a KV broadcast (rank 0 publishes, all ranks observe),
  - a KV all-gather + 4-way barrier across process boundaries,
and in the fault test pod 1's workers SIGKILL themselves on first deploy
while pod 0's ranks are already parked in the barrier — the launcher's
watch loop must relaunch the pod and the job must still converge.
"""
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import os, signal, sys, time
from paddle_tpu.distributed.store import TCPStore

rank = int(os.environ["PADDLE_TRAINER_ID"])
world = int(os.environ["PADDLE_TRAINERS_NUM"])
node = os.environ["PADDLE_NODE_RANK"]
marker_dir = os.environ["MARKER_DIR"]

# Fault injection: on the first deploy of the designated pod, die by
# SIGKILL (a real kill, exit code -9) before touching the store. Each
# worker marks itself, then waits for its sibling's mark before it dies:
# a sibling torn down by the pod's restart before it marked would die on
# the relaunch instead, after this worker had joined the barrier, and that
# stale arrival would leave the relaunched pod's barrier one short forever.
if os.environ.get("FAIL_NODE") == node:
    local = int(os.environ["PADDLE_LOCAL_RANK"])
    marker = os.path.join(marker_dir, "ran_%s_%d" % (node, local))
    sibling = os.path.join(marker_dir, "ran_%s_%d" % (node, 1 - local))
    if not os.path.exists(marker):
        open(marker, "w").close()
        deadline = time.monotonic() + 300
        while not os.path.exists(sibling) and time.monotonic() < deadline:
            time.sleep(0.05)
        os.kill(os.getpid(), signal.SIGKILL)

# env contract
eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
assert len(eps) == world, eps
assert os.environ["PADDLE_CURRENT_ENDPOINT"] == eps[rank]
assert os.environ["JAX_PROCESS_ID"] == str(rank)
assert os.environ["PADDLE_NNODES"] == "2"
assert int(os.environ["PADDLE_LOCAL_RANK"]) == rank % 2

host, port = os.environ["PADDLE_MASTER"].rsplit(":", 1)
store = TCPStore(host, int(port), world_size=world, timeout=600)

# broadcast: rank 0 publishes, everyone blocks until visible
if rank == 0:
    store.set("bcast/meta", "job=%s world=%d" %
              (os.environ["PADDLE_JOB_ID"], world))
store.wait("bcast/meta", timeout=600)
bcast = store.get("bcast/meta").decode()

# KV all-gather + 4-way barrier spanning both pods
store.set("ag/%d" % rank, str(rank * 10))
store.barrier("work", timeout=600)
vals = [int(store.get("ag/%d" % r).decode()) for r in range(world)]
assert vals == [r * 10 for r in range(world)], vals

with open(os.path.join(marker_dir, "done_%d" % rank), "w") as f:
    f.write(bcast + "|" + str(sum(vals)))

# no store traffic after this barrier: pod 0 may exit (and take the
# master server with it) the moment its own ranks return
store.barrier("exit", timeout=600)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _launch_pod(node_rank, master, script, tmp_path, extra_env=None,
                max_restart=0):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               MARKER_DIR=str(tmp_path))
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "2", "--nproc_per_node", "2",
         "--master", master, "--rank", str(node_rank),
         "--job_id", "itest", "--max_restart", str(max_restart),
         "--log_dir", str(tmp_path / ("logs%d" % node_rank)),
         str(script)],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


def _run_job(tmp_path, pod1_env=None, max_restart=0):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    master = "127.0.0.1:%d" % _free_port()
    pod0 = _launch_pod(0, master, script, tmp_path)
    pod1 = _launch_pod(1, master, script, tmp_path, extra_env=pod1_env,
                       max_restart=max_restart)
    try:
        out0, _ = pod0.communicate(timeout=900)
        out1, _ = pod1.communicate(timeout=900)
    finally:
        for p in (pod0, pod1):
            if p.poll() is None:
                p.kill()
    return pod0.returncode, pod1.returncode, out0, out1


def _assert_job_converged(tmp_path):
    done = sorted(tmp_path.glob("done_*"))
    assert [d.name for d in done] == ["done_%d" % r for r in range(4)]
    texts = {d.read_text() for d in done}
    # every rank saw the same broadcast and the same gathered sum
    assert texts == {"job=itest world=4|60"}


def test_two_pods_rendezvous_broadcast_barrier(tmp_path):
    rc0, rc1, out0, out1 = _run_job(tmp_path)
    assert rc0 == 0, out0
    assert rc1 == 0, out1
    _assert_job_converged(tmp_path)


def test_pod_killed_and_relaunched(tmp_path):
    rc0, rc1, out0, out1 = _run_job(
        tmp_path, pod1_env={"FAIL_NODE": "1"}, max_restart=2)
    assert rc1 == 0, out1
    assert "restart 1/2" in out1, out1
    assert rc0 == 0, out0
    _assert_job_converged(tmp_path)
    # both of pod 1's workers really died once (SIGKILL path)
    assert (tmp_path / "ran_1_0").exists() and (tmp_path / "ran_1_1").exists()
