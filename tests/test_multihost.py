"""REAL multi-process distributed execution: two OS processes form a
jax.distributed CPU cluster through the launcher's env contract and run a
cross-process psum (the reference's multi-node NCCL path, test pattern:
test_dist_base.py subprocess clusters — no fake backend)."""
import os
import socket
import subprocess
import sys

import pytest

import jax

# the workers pin jax_platforms=cpu, and the pinned jaxlib's CPU client
# has no cross-process collectives (gloo landed behind
# jax_cpu_collectives_implementation on later jax) — the 2-proc cluster
# dies at its first psum on any host
pytestmark = pytest.mark.skipif(
    not hasattr(jax.config, "jax_cpu_collectives_implementation"),
    reason="pinned jaxlib: no CPU cross-process collectives")

_WORKER = r"""
import os
import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np

import paddle_tpu as paddle

# launcher env contract (PADDLE_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID)
# drives jax.distributed.initialize inside init_parallel_env
paddle.distributed.init_parallel_env({"dp": 2})
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = paddle.distributed.get_mesh()
assert len(jax.devices()) == 2, jax.devices()

g = shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
              in_specs=P("dp"), out_specs=P())
arr = jax.make_array_from_callback(
    (2, 4), NamedSharding(mesh, P("dp")),
    lambda idx: np.ones((1, 4), np.float32) * (jax.process_index() + 1))
out = g(arr)
val = np.asarray(jax.device_get(out.addressable_shards[0].data)).ravel()[0]
assert val == 3.0, val  # 1 + 2 summed across processes
print(f"MULTIHOST-OK-{jax.process_index()}", flush=True)
"""


_REDUCER_WORKER = r"""
import os
import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn

paddle.distributed.init_parallel_env({"dp": 2})
r = jax.process_index()

model = nn.Linear(4, 1)
model.weight._value = jax.numpy.zeros((4, 1), "float32")  # identical init
dp = paddle.DataParallel(model)  # process_count()==2 -> Reducer auto-on
assert dp._reducer is not None

# DIFFERENT local batch per rank: local grad_w = 3*(r+1) per entry,
# so the reduced (mean) grad must be (3*1 + 3*2)/2 = 4.5 on BOTH ranks
x = paddle.to_tensor(np.full((3, 4), float(r + 1), np.float32))
loss = paddle.sum(dp(x))
loss.backward()
dp.sync_gradients()
g = np.asarray(model.weight.grad.value)
assert np.allclose(g, 4.5), (r, g)
print(f"REDUCER-OK-{r}", flush=True)
"""


def _free_port_pair():
    """env.py advertises the KV port and binds jax coordination on port+1 —
    both must be free."""
    for _ in range(64):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        try:
            s2 = socket.socket()
            s2.bind(("127.0.0.1", port + 1))
            s2.close()
            return port
        except OSError:
            continue
    raise RuntimeError("no free consecutive port pair")


def _run_cluster(tmp_path, source, marker):
    port = _free_port_pair()
    script = tmp_path / "worker.py"
    script.write_text(source)
    procs = []
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for pid in range(2):
        env = dict(os.environ,
                   PADDLE_TPU_COORDINATOR=f"127.0.0.1:{port}",
                   PADDLE_TPU_NUM_PROCESSES="2",
                   PADDLE_TPU_PROCESS_ID=str(pid),
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [repo_root] + ([os.environ["PYTHONPATH"]]
                                      if os.environ.get("PYTHONPATH")
                                      else [])))
        env.pop("XLA_FLAGS", None)  # one local device per process
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    for pid, out in enumerate(outs):
        assert f"{marker}-{pid}" in out, out[-2000:]


def test_two_process_psum(tmp_path):
    _run_cluster(tmp_path, _WORKER, "MULTIHOST-OK")


def test_two_process_reducer_parity(tmp_path):
    """Eager DataParallel across REAL processes: per-rank local grads
    differ; the Reducer's fused bucket pmean must land the cross-process
    mean on every rank (reference reducer.cc allreduce parity)."""
    _run_cluster(tmp_path, _REDUCER_WORKER, "REDUCER-OK")
