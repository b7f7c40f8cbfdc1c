"""Elastic WORLD RESIZE: preemption -> resume at a smaller world ->
relaunch -> resume at the full world, losses matching an uninterrupted run.

The reference rescales within an np range by rewriting endpoints and
relaunching (ref:python/paddle/distributed/fleet/elastic/manager.py:124,
220-255). Here ``launch --elastic_level 2 --np 1:2`` relaunches the pod at
the SURVIVING world size; each incarnation rebuilds its data-parallel view
from the new PADDLE_TRAINERS_NUM and resumes from TrainCheckpointer.

The train script is deterministic full-batch data-parallel: each rank
computes the gradient of its equal shard, shard grads are exchanged through
the TCPStore and averaged identically on every rank — so the parameter
trajectory is EXACTLY world-size-independent and losses must match an
uninterrupted single-world control step for step.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

TRAIN_SCRIPT = r"""
import os, pickle, signal, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.checkpoint import TrainCheckpointer
from paddle_tpu.distributed.store import TCPStore

work = sys.argv[1]
kill_at = int(sys.argv[2])          # -1: never (control)
total_steps = int(sys.argv[3])
rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))

mhost, mport = os.environ["PADDLE_MASTER"].rsplit(":", 1)
store = TCPStore(mhost, int(mport), is_master=(rank == 0), world_size=world)

paddle.seed(11)
net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))

ckpt = TrainCheckpointer(os.path.join(work, "ckpt"), max_to_keep=2)
start = 0
latest = ckpt.latest_step()
if latest is not None:
    restored = ckpt.restore()
    net.set_state_dict(restored["model"])
    start = latest + 1
first_incarnation = latest is None

lr = 0.05
rng = np.random.RandomState(0)
X = rng.rand(64, 4).astype(np.float32)
wtrue = np.array([1.0, -2.0, 0.5, 3.0], np.float32)
Y = (X @ wtrue)[:, None]
# equal contiguous shards: world divides 64, every shard mean is the full
# mean when averaged -> trajectory identical at any world size
shard = 64 // world
Xs, Ys = X[rank*shard:(rank+1)*shard], Y[rank*shard:(rank+1)*shard]

with open(os.path.join(work, f"losses.{rank}.w{world}.log"), "a") as f:
    f.write(f"# start={start} world={world}\n"); f.flush()
    for step in range(start, total_steps):
        xb, yb = paddle.to_tensor(Xs), paddle.to_tensor(Ys)
        loss = ((net(xb) - yb) ** 2).mean()
        loss.backward()
        # deterministic DP allreduce through the store: every rank posts
        # its shard grads, reads all, averages identically
        grads = [p.grad.numpy() for p in net.parameters()]
        store.set(f"g/{step}/{rank}", pickle.dumps(grads).hex())
        acc = None
        for r in range(world):
            g = pickle.loads(bytes.fromhex(
                store.wait(f"g/{step}/{r}").decode()))
            acc = g if acc is None else [a + b for a, b in zip(acc, g)]
        for p, g in zip(net.parameters(), acc):
            p._data = p._data - lr * (np.asarray(g) / world)
            p.clear_grad()
        # full-batch loss for comparison (shard loss differs per rank)
        full = float(((net(paddle.to_tensor(X)) - paddle.to_tensor(Y)) ** 2
                      ).mean().numpy())
        if rank == 0:
            ckpt.save(step, {"model": net.state_dict()})
            ckpt.wait_until_finished()
        f.write(f"{step} {full:.6f}\n"); f.flush()
        if (first_incarnation and kill_at >= 0 and step == kill_at
                and rank == world - 1 and world > 1):
            os.kill(os.getpid(), signal.SIGKILL)   # simulated preemption
        store.barrier(f"step{step}")
store.close()
"""


def _launch(tmp_path, name, kill_at, steps, nproc, extra=()):
    work = tmp_path / name
    work.mkdir(exist_ok=True)
    script = work / "train.py"
    script.write_text(TRAIN_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="/root/repo")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(nproc), "--log_dir", str(work / "logs"),
           *extra, str(script), str(work), str(kill_at), str(steps)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=420, cwd=str(tmp_path))
    return work, r


def _losses(work):
    """step -> full-batch loss, merged over every rank-0 incarnation log
    (later incarnations overwrite: resumed steps win)."""
    out = {}
    for p in sorted(work.glob("losses.0.w*.log")):
        for line in p.read_text().splitlines():
            if line.startswith("#"):
                continue
            s, l = line.split()
            out[int(s)] = float(l)
    return out


@pytest.mark.slow
def test_world_resize_resume(tmp_path):
    steps1, steps2 = 8, 12

    # control: uninterrupted world=2 for steps2 steps
    work_c, rc = _launch(tmp_path, "control", kill_at=-1, steps=steps2,
                         nproc=2)
    assert rc.returncode == 0, rc.stderr[-2000:]
    control = _losses(work_c)
    assert sorted(control) == list(range(steps2))

    # phase 1: world=2, rank1 preempted at step 4 -> elastic_level 2
    # relaunches at world=1; training resumes from ckpt and finishes steps1
    work_p, rp = _launch(tmp_path, "resize", kill_at=4, steps=steps1,
                         nproc=2,
                         extra=("--elastic_level", "2", "--np", "1:2",
                                "--max_restart", "3"))
    assert rp.returncode == 0, rp.stderr[-2000:]
    assert "rescaling world 2 -> 1" in rp.stderr, rp.stderr[-2000:]
    phase1 = _losses(work_p)
    assert sorted(phase1) == list(range(steps1))
    # the world=1 incarnation actually ran (scale-in happened)
    assert list(work_p.glob("losses.0.w1.log")), "no world=1 resume log"

    # phase 2: scale back OUT — a fresh world=2 launch resumes from the
    # same checkpoint directory and continues to steps2
    work_p2, rp2 = _launch(tmp_path, "resize", kill_at=-1, steps=steps2,
                           nproc=2)
    assert rp2.returncode == 0, rp2.stderr[-2000:]
    phase2 = _losses(work_p2)
    assert sorted(phase2) == list(range(steps2))

    # the interrupted+rescaled trajectory equals the uninterrupted control
    for s in range(steps2):
        np.testing.assert_allclose(phase2[s], control[s], rtol=1e-5,
                                   err_msg=f"step {s}")


def test_propose_world_clamps_to_np_range():
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.store import TCPStore

    store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    try:
        m = ElasticManager(store, rank=0, world_size=4, lease=2.0,
                           min_np=2, max_np=4)
        # ranks 0..2 alive, rank 3 dead
        for r in range(3):
            store.set(f"hb/{r}", repr(__import__("time").time()))
        assert m.live_world() == 3
        assert m.propose_world() == 3
        # only one survivor: below min_np -> cannot continue
        store.set("hb/1", "0")
        store.set("hb/2", "0")
        assert m.live_world() == 1
        assert m.propose_world() is None
    finally:
        store.close()
