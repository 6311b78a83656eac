"""Elastic relaunch + AutoCheckpoint kill-test (reference auto_checkpoint.py
+ fleet/elastic.py:125-164): a 2-process pod trains with per-step sharded
checkpoints; one rank is SIGKILLed mid-run; the launcher relaunches the pod
and training RESUMES from the newest loadable sharded step, reaching the
exact same final state as an uninterrupted run.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

_TRAIN = r"""
import os, signal, sys, time
os.environ.pop("XLA_FLAGS", None)  # one local device per process
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
from jax.sharding import NamedSharding, PartitionSpec as P
from paddle_tpu.framework.checkpoint import AutoCheckpoint

paddle.distributed.init_parallel_env({"dp": 2})
mesh = paddle.distributed.get_mesh()
rank = jax.process_index()
ckpt = os.environ["TEST_CKPT_DIR"]
marker = os.environ["TEST_MARKER"]
TOTAL = 12

# dp-sharded state: each process owns one row of w
sh = NamedSharding(mesh, P("dp"))
w = jax.make_array_from_callback(
    (2, 8), sh, lambda idx: np.zeros((2, 8), np.float32)[idx])
state = {"w": w}
acp = AutoCheckpoint(ckpt, every_steps=1, keep_max=6)
state, start = acp.resume(state)
print(f"rank {rank} resumed at step {start}", flush=True)

# real training steps carry collectives: when a peer dies, the survivor's
# next psum fails instead of letting it race ahead solo and pollute the
# checkpoint dir with rank-partial saves
from jax import shard_map
couple = jax.jit(shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                           in_specs=P("dp"), out_specs=P(),
                           check_vma=False))

for step in range(start + 1, TOTAL + 1):
    state = {"w": jax.jit(lambda a, s: a + s, out_shardings=sh,
                          static_argnums=1)(state["w"], float(step))}
    if rank == 1 and step == 6 and not os.path.exists(marker):
        open(marker, "w").close()
        os.kill(os.getpid(), signal.SIGKILL)  # die BEFORE saving step 6
    couple(state["w"]).block_until_ready()  # cross-rank coupling
    acp.maybe_save(state, step)

mine = np.asarray(state["w"].addressable_shards[0].data)
expect = sum(range(1, TOTAL + 1))  # 78: exact resume-and-continue math
assert np.allclose(mine, expect), (rank, mine)
open(os.environ["TEST_DONE"] + f".{rank}", "w").write(str(float(mine.ravel()[0])))
print(f"rank {rank} DONE {mine.ravel()[0]}", flush=True)
"""


# the worker script pins jax_platforms=cpu, and the pinned jaxlib's CPU
# client has no cross-process collectives (the gloo implementation landed
# behind jax_cpu_collectives_implementation on later jax) — the 2-proc pod
# then dies at its first psum with "Multiprocess computations aren't
# implemented on the CPU backend", on any host
@pytest.mark.skipif(
    not hasattr(jax.config, "jax_cpu_collectives_implementation"),
    reason="pinned jaxlib: no CPU cross-process collectives")
def test_kill_rank_resumes_from_sharded_checkpoint(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(_TRAIN)
    ckpt = tmp_path / "ckpt"
    marker = tmp_path / "killed"
    done = tmp_path / "done"
    log_dir = tmp_path / "logs"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               TEST_CKPT_DIR=str(ckpt), TEST_MARKER=str(marker),
               TEST_DONE=str(done),
               PYTHONPATH=os.pathsep.join(
                   [repo] + ([os.environ["PYTHONPATH"]]
                             if os.environ.get("PYTHONPATH") else [])))
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_host", "2", "--coordinator", "127.0.0.1:0",
         "--max_restarts", "2", "--log_dir", str(log_dir), str(script)],
        cwd="/root/repo", capture_output=True, text=True, timeout=600,
        env=env)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert marker.exists(), "the kill never happened"
    assert "pod restart" in r.stderr, r.stderr[-2000:]
    # both ranks finished with the exact uninterrupted-run state (resume
    # restored the sharded snapshot, then the remaining steps re-ran)
    for rank in (0, 1):
        f = tmp_path / f"done.{rank}"
        assert f.exists(), (rank, r.stderr[-2000:])
        assert float(f.read_text()) == float(sum(range(1, 13)))
    # the relaunched pod really resumed from a checkpoint, not step 0 —
    # and BOTH ranks agreed on the step (verify_step's global completeness
    # check; divergent per-rank resume would deadlock real collectives)
    per_rank = {}
    for p in os.listdir(log_dir):
        rank = int(p.split(".")[1])
        per_rank[rank] = [int(line.rsplit("step", 1)[1])
                          for line in (log_dir / p).read_text().splitlines()
                          if "resumed at step" in line]
    finals = {r: v[-1] for r, v in per_rank.items() if v}
    assert len(finals) == 2 and len(set(finals.values())) == 1, per_rank
    assert next(iter(finals.values())) >= 4, per_rank
