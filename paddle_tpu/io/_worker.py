"""Standalone DataLoader worker-process entry (reference
dataloader_iter.py:248 _worker_loop).

Run BY FILE PATH (``python <this file> <cmd_fd> <res_fd>``), never via
``-m``: executing by path keeps the child free of both the parent's
``__main__`` re-import (the multiprocessing-spawn pitfall that re-runs
unguarded user scripts) and the paddle_tpu package import — the child
imports exactly stdlib + numpy + whatever the pickled dataset needs.
The parent sets JAX_PLATFORMS=cpu / PADDLE_TPU_WORKER_ID in the child's
env, so even a jax-importing dataset can never claim the chip.

Frame protocol (length-prefixed pickle, request/response lockstep):
  parent→child:  (sys_path,)  then  (dataset, worker_init_fn, wid, nw, seed)
                 then  (i, idxs, rseed) per batch;  None = clean shutdown
  child→parent:  (i, samples, None)  or  (i, None, traceback_str)

``rseed`` (when not None) reseeds the child's global numpy RNG before
serving batch ``i``: the parent derives it from (per-epoch base, batch
index), so worker-side augmentation depends only on the batch — identical
across runs regardless of which child the work-stealing queue hands the
batch to, and fresh each epoch even for a persistent pool.
"""
import os
import pickle
import struct
import sys
import traceback


def read_frame(f):
    hdr = f.read(8)
    if len(hdr) < 8:
        return None
    (n,) = struct.unpack("<Q", hdr)
    payload = f.read(n)
    if len(payload) < n:
        return None
    return pickle.loads(payload)


def write_frame(f, obj):
    b = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    f.write(struct.pack("<Q", len(b)))
    f.write(b)
    f.flush()


def main(argv):
    inp = os.fdopen(int(argv[1]), "rb")
    out = os.fdopen(int(argv[2]), "wb")
    frame = read_frame(inp)
    if frame is None:
        return 0
    (paths,) = frame
    for p in reversed(paths):
        if p not in sys.path:
            sys.path.insert(0, p)
    hello = read_frame(inp)
    if hello is None:
        return 0
    dataset, init_fn, wid, nw, seed = hello
    import numpy as np

    np.random.seed(seed % (2 ** 32))
    if init_fn is not None:
        init_fn(wid)
    while True:
        msg = read_frame(inp)
        if msg is None:
            return 0
        i, idxs = msg[0], msg[1]
        rseed = msg[2] if len(msg) > 2 else None
        try:
            if rseed is not None:
                np.random.seed(rseed % (2 ** 32))
            write_frame(out, (i, [dataset[j] for j in idxs], None))
        except BaseException:
            write_frame(out, (i, None, traceback.format_exc()))


if __name__ == "__main__":
    try:
        sys.exit(main(sys.argv))
    except (BrokenPipeError, EOFError, KeyboardInterrupt):
        sys.exit(0)
