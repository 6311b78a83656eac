"""Disaggregated serving fleet: prefill/decode split + a telemetry router.

The reference dedicates ~20k LoC to distributed serving infrastructure
(``fluid/distributed``: a param-server fleet over brpc) and a 47k-LoC
inference layer of per-thread predictors.  This module is the jax-era
equivalent at LLM-serving granularity — three legs that compose the
pieces earlier rounds built:

* **Tensor-parallel decode inside the server** lives in
  ``serving.DecodeServer(mesh=...)`` (round 9): the batched tick runs
  Megatron-sharded through the same step getters, the paged pool's Hkv
  axis sharding like the slab's head axis
  (``generate.sharded_cache_specs``), donation/jit-key/recompile-watch
  composing unchanged.
* **Prefill/decode disaggregation**: :class:`PrefillWorker` runs
  admission prefill OFF the token loop — the same bucketed executables
  the decode replica would run locally (the Engine's ``prefill`` /
  ``paged_prefill`` registry kinds), on its own single-slot cache — and
  streams
  the finished cache rows + admission logits back over a pluggable
  transport (:class:`LoopbackTransport` in-process for tests/CPU,
  :class:`SocketTransport` TCP frames for real fleets).  The decode side
  injects them via ``DecodeServer.submit_prefilled`` (one donated
  injector executable per bucket; paged: scattered through the block
  table), so decode proceeds BIT-IDENTICALLY to local admission while
  long prompts never stall TPOT.
* **A multi-replica** :class:`Router` front-end: admission, priority and
  TTL-aware shedding at the fleet queue, load balancing on the exact
  quantities the telemetry gauges sample (queue depth, slot occupancy,
  KV utilization — read per replica via ``DecodeServer.load_stats``),
  per-replica health aggregation (a wedged replica is drained and its
  queued work re-routed onto survivors, leaning on the round-7 wedge
  recovery for its active slots), and fleet-level Prometheus export
  (``fleet.*`` counters/gauges land in the shared registry, so
  ``Router(metrics_port=...)`` serves them next to the serving feeds).

Transport frames are a dtype-tagged raw-row streaming protocol — a
compact JSON/struct header (leaf names, shapes, dtypes, rid, chunk
index) followed by contiguous raw buffer frames (``memoryview`` from
the sender's numpy rows straight to the socket, reassembled into
writable buffers for ``device_put``).  NOTHING on the wire is pickled:
the control plane is JSON, the data plane raw bytes, so a compromised
peer can corrupt rows but never execute code in the receiver.  The
links still carry model activations between co-owned processes (the
weights' trust domain) — never expose a transport port beyond it.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import json
import os
import queue
import socket
import struct
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import admission as _admission
from . import engine as _engine
from . import generate, gpt, kv_pool as _kv, serving
from .. import flags as _flags
from .. import resilience as _resilience
from .. import telemetry as _telemetry

__all__ = [
    "LoopbackTransport", "SocketTransport", "PrefillWorker", "Router",
    "serve_prefill_worker",
]


# ---------------------------------------------------------------------------
# transports: one message-passing shape, two fabrics
# ---------------------------------------------------------------------------


class _QueueEndpoint:
    """One side of an in-process transport (a pair of ``queue.Queue``)."""

    def __init__(self, send_q: queue.Queue, recv_q: queue.Queue):
        self._send = send_q
        self._recv = recv_q

    def send(self, obj) -> None:
        self._send.put(obj)

    def recv(self, timeout: float = 0.0):
        """Next message, or None when none arrives within ``timeout``."""
        try:
            if timeout and timeout > 0:
                return self._recv.get(timeout=timeout)
            return self._recv.get_nowait()
        except queue.Empty:
            return None

    def close(self) -> None:
        pass


class LoopbackTransport:
    """In-process endpoint pair (tests, CPU fleets, co-located workers):
    ``.client`` is the router's side, ``.worker`` the prefill worker's —
    messages pass by reference, zero serialization."""

    def __init__(self):
        a, b = queue.Queue(), queue.Queue()
        self.client = _QueueEndpoint(a, b)
        self.worker = _QueueEndpoint(b, a)


# a frame (or a headered message awaiting its buffer frames) the peer
# started but never finished within this budget is a dead link, not a
# slow one
_FRAME_BUDGET_S = 30.0

# typed wire frames: 1-byte frame type + 8-byte big-endian body length.
# A message is ONE header frame (JSON: the object tree with every
# ndarray leaf replaced by a {"__nd__", "shape", "dtype"} descriptor)
# followed by exactly header["nbufs"] raw buffer frames, one per
# descriptor, in index order.  The data plane never touches a
# serializer: buffer bodies go out as memoryviews of the sender's
# contiguous numpy rows and come back as writable bytearrays the
# receiver wraps with np.frombuffer — ready for device_put with zero
# further copies.
_F_HDR = 1
_F_BUF = 2
_FRAME_PREFIX = struct.Struct(">BQ")

# scatter-gather writes hand the kernel at most this many iovecs per
# sendmsg call (POSIX IOV_MAX is commonly 1024; staying under it keeps
# one syscall per *message* for every realistic frame count)
_SENDMSG_MAX_FRAMES = 512


def _send_frames(sock: socket.socket, frames: list) -> None:
    """ONE gathered write for a whole message — the frame prefixes, the
    JSON header, and every raw buffer frame go down in a single
    ``sendmsg`` (scatter-gather) call instead of 1 + 2*nbufs ``sendall``
    round trips, each of which could flush a sub-MTU segment and stall
    the decode-side reader between a header and its rows.  The bytes on
    the wire are IDENTICAL to the per-frame path (pinned by the codec
    round-trip tests); only the syscall batching changes.  Partial
    sends (socket buffer full) resume from the exact offset; platforms
    without ``sendmsg`` fall back to per-frame ``sendall``."""
    if not hasattr(sock, "sendmsg"):
        for f in frames:
            sock.sendall(f)
        return
    views = []
    for f in frames:
        mv = f if isinstance(f, memoryview) else memoryview(f)
        views.append(mv.cast("B") if mv.ndim != 1 or mv.format != "B"
                     else mv)
    while views:
        try:
            sent = sock.sendmsg(views[:_SENDMSG_MAX_FRAMES])
        except InterruptedError:
            continue
        while views and sent >= views[0].nbytes:
            sent -= views[0].nbytes
            views.pop(0)
        if views and sent:
            views[0] = views[0][sent:]


def _np_dtype(name: str):
    """Resolve a wire dtype name, including the ml_dtypes extension
    types (bfloat16 & friends) plain numpy does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _encode_msg(obj):
    """Split one message into (json_header_bytes, [ndarray, ...]).

    The header is the object tree with ndarray leaves swapped for
    buffer descriptors; the arrays ride separately as raw frames.
    Only JSON-safe scalars, lists/tuples, string-keyed dicts and
    ndarrays are legal — anything else is a protocol bug and raises
    (never a silent pickle fallback)."""
    bufs: list = []

    def enc(v):
        if isinstance(v, np.ndarray):
            a = np.ascontiguousarray(v)
            bufs.append(a)
            return {"__nd__": len(bufs) - 1,
                    "shape": list(a.shape), "dtype": a.dtype.name}
        if isinstance(v, np.generic):
            return v.item()
        if isinstance(v, dict):
            if any(not isinstance(k, str) for k in v):
                raise TypeError("transport dict keys must be str")
            return {k: enc(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [enc(x) for x in v]
        if v is None or isinstance(v, (bool, int, float, str)):
            return v
        raise TypeError(
            f"type {type(v).__name__} is not transportable (the wire "
            f"carries JSON scalars + raw ndarray frames, never pickle)")

    tree = enc(obj)
    hdr = json.dumps({"o": tree, "nbufs": len(bufs)},
                     separators=(",", ":")).encode("utf-8")
    return hdr, bufs


def _decode_msg(hdr: bytes, bufs: list):
    """Inverse of :func:`_encode_msg`: rebuild the object tree, wrapping
    each received (writable) buffer as an ndarray view."""
    top = json.loads(hdr.decode("utf-8"))

    def dec(v):
        if isinstance(v, dict):
            if "__nd__" in v:
                a = np.frombuffer(bufs[v["__nd__"]],
                                  dtype=_np_dtype(v["dtype"]))
                return a.reshape(v["shape"])
            return {k: dec(x) for k, x in v.items()}
        if isinstance(v, list):
            return [dec(x) for x in v]
        return v

    if len(bufs) != top.get("nbufs", 0):
        raise ConnectionError(
            f"transport message carried {len(bufs)} buffer frames, "
            f"header promised {top.get('nbufs', 0)}")
    return dec(top["o"])


class _SocketEndpoint:
    """Typed frames over one TCP socket (same send/recv surface as the
    loopback endpoint).  Writes are locked (whole messages, atomic
    w.r.t. other senders on this endpoint); reads buffer partial frames
    AND partially-received multi-frame messages across ``recv`` calls,
    so a poll timeout never tears either."""

    def __init__(self, sock: socket.socket):
        self._sock = sock
        self._wlock = threading.Lock()
        self._buf = bytearray()
        self._scratch = bytearray(1 << 20)   # recv_into target, reused
        self._hdr: bytes | None = None   # parsed header awaiting buffers
        self._need = 0                   # buffer frames still expected
        self._bufs: list = []            # buffer frames received so far

    def send(self, obj) -> None:
        hdr, arrs = _encode_msg(obj)
        frames = [_FRAME_PREFIX.pack(_F_HDR, len(hdr)), hdr]
        for a in arrs:
            # zero-copy data plane: the rows' own buffer feeds the
            # socket — no serializer, no intermediate bytes object.
            # Extension dtypes (ml_dtypes bfloat16 & friends) refuse
            # the buffer protocol directly; a uint8 VIEW of the same
            # memory is still zero-copy and byte-identical
            try:
                mv = memoryview(a).cast("B")
            except (ValueError, TypeError):
                mv = memoryview(a.reshape(-1).view(np.uint8))
            frames.append(_FRAME_PREFIX.pack(_F_BUF, mv.nbytes))
            frames.append(mv)
        with self._wlock:
            _send_frames(self._sock, frames)
        if _telemetry.enabled():
            _telemetry.count("fleet.frame_batches")

    def _pop_frame(self):
        """(ftype, body) of the next complete frame in the read buffer,
        or None.  The body of a buffer frame is a fresh writable
        bytearray — exactly what device_put-bound np.frombuffer wants."""
        if len(self._buf) < 9:
            return None
        ftype, ln = _FRAME_PREFIX.unpack_from(self._buf)
        if len(self._buf) < 9 + ln:
            return None
        body = bytearray(self._buf[9:9 + ln])
        del self._buf[:9 + ln]
        return ftype, body

    def _pump(self):
        """Fold complete frames into the message assembler; returns a
        finished message's decoded object, else None."""
        while True:
            fr = self._pop_frame()
            if fr is None:
                return None
            ftype, body = fr
            if ftype == _F_HDR:
                if self._hdr is not None:
                    raise ConnectionError(
                        "transport header frame arrived mid-message")
                try:
                    need = json.loads(bytes(body).decode("utf-8")).get(
                        "nbufs", 0)
                except (ValueError, UnicodeDecodeError) as e:
                    raise ConnectionError(
                        f"malformed transport header: {e}") from e
                if need == 0:
                    return _decode_msg(bytes(body), [])
                self._hdr, self._need, self._bufs = bytes(body), need, []
            elif ftype == _F_BUF:
                if self._hdr is None:
                    raise ConnectionError(
                        "transport buffer frame without a header")
                self._bufs.append(body)
                if len(self._bufs) == self._need:
                    hdr, bufs = self._hdr, self._bufs
                    self._hdr, self._need, self._bufs = None, 0, []
                    return _decode_msg(hdr, bufs)
            else:
                raise ConnectionError(
                    f"unknown transport frame type {ftype}")

    def recv(self, timeout: float = 0.0):
        deadline = time.perf_counter() + max(float(timeout), 0.0)
        frame_deadline = None
        tried = False
        while True:
            msg = self._pump()
            if msg is not None:
                return msg
            mid = bool(self._buf) or self._hdr is not None
            if mid and frame_deadline is None:
                # ANY partial frame or headered-but-unfinished message
                # arms the budget — a peer stalling mid-header is as
                # dead as one stalling between a header and its buffer
                # frames, and a partial CHUNK must never wedge the
                # reader past this bound
                frame_deadline = time.perf_counter() + _FRAME_BUDGET_S
            rem = deadline - time.perf_counter()
            if mid:
                # mid-message: wait for the rest (bounded by the frame
                # budget), even past the caller's poll timeout
                rem = max(rem, 0.05)
                if time.perf_counter() > frame_deadline:
                    raise ConnectionError(
                        "torn transport frame (peer died mid-send?)")
            elif rem <= 0 and tried:
                # timeout 0 is a POLL: at least one non-blocking read
                # attempt runs before giving up
                return None
            tried = True
            self._sock.settimeout(max(rem, 1e-3))
            try:
                # recv_into the preallocated scratch: no fresh 1 MiB
                # bytes object per wakeup — the kernel writes straight
                # into the reused bytearray and only the received span
                # is appended to the assembler buffer
                n = self._sock.recv_into(self._scratch)
            except socket.timeout:
                continue
            except ConnectionError:
                raise
            except OSError as e:
                # ECONNRESET and friends are OSErrors too: an abortive
                # peer death must raise like an orderly one, never read
                # as an idle link
                raise ConnectionError(
                    f"transport socket error: {e}") from e
            if not n:
                # orderly shutdown: the peer is GONE, not idle — raise
                # so the router can fail outstanding work instead of
                # polling a dead link forever
                raise ConnectionError(
                    "transport closed mid-frame" if mid
                    else "transport closed by peer")
            self._buf += memoryview(self._scratch)[:n]

    def close(self) -> None:
        with contextlib.suppress(OSError):
            self._sock.close()


class _SocketListener:
    def __init__(self, srv: socket.socket):
        self._srv = srv
        self.port = srv.getsockname()[1]

    def accept(self, timeout: float = 30.0) -> _SocketEndpoint:
        self._srv.settimeout(timeout)
        sock, _ = self._srv.accept()
        return _SocketEndpoint(sock)

    def close(self) -> None:
        with contextlib.suppress(OSError):
            self._srv.close()


class SocketTransport:
    """TCP transport for cross-process fleets: ``listen`` on the worker
    host, ``connect`` from the router.  Frames are JSON headers + raw
    buffer frames (never pickle) — the link carries cache rows between
    co-owned processes (the weights' trust domain); never expose the
    port beyond it."""

    @staticmethod
    def listen(host: str = "127.0.0.1", port: int = 0) -> _SocketListener:
        srv = socket.create_server((host, int(port)))
        return _SocketListener(srv)

    @staticmethod
    def connect(host: str, port: int,
                timeout: float = 30.0) -> _SocketEndpoint:
        return _SocketEndpoint(
            socket.create_connection((host, int(port)), timeout=timeout))


# ---------------------------------------------------------------------------
# prefill worker: admission prefill off the token loop
# ---------------------------------------------------------------------------


class PrefillWorker:
    """Dedicated prefill engine: one slot, the SAME bucketed admission
    executables a ``DecodeServer`` runs locally — so the rows it streams
    to a decode replica produce bit-identical greedy decode.

    ``layout`` must match the decode replicas' (the two layouts' prefill
    math differs in reduction shape, and bit-parity is the contract);
    ``device`` pins the worker's compute to one chip so fleet prefill
    runs beside, not inside, the decode replicas' devices.  Drive it
    cooperatively (:meth:`run_once`) or as a daemon thread
    (:meth:`start`) consuming ``{"rid", "prompt"}`` jobs from
    ``endpoint`` and answering ``{"rid", "rows", "logits"}`` (or
    ``{"rid", "error"}``)."""

    def __init__(self, params, cfg: gpt.GPTConfig, max_len: int,
                 layout: str | None = None, block_size: int | None = None,
                 endpoint=None, device=None, name: str = "prefill"):
        lay = layout if layout is not None else _flags.kv_layout()
        if lay not in ("contiguous", "paged"):
            raise ValueError(
                f"layout {lay!r}: expected 'contiguous' or 'paged'")
        if cfg.ssm is not None:
            raise NotImplementedError(
                "PrefillWorker: a config with an ssm mixer cannot hand a "
                "prefill off yet — the handoff ships KV rows, and the "
                "recurrent state the prompt leaves has no wire form")
        if cfg.mla is not None:
            raise NotImplementedError(
                "PrefillWorker: a latent-attention config cannot hand a "
                "prefill off yet — the handoff ships K/V rows, and a "
                "latent row has no wire form")
        self.cfg = cfg
        self.max_len = int(max_len)
        self.name = name
        self.endpoint = endpoint
        self._paged = lay == "paged"
        self._device = device
        # placement joins the step-cache keys (serving._shard_key): two
        # workers pinned to different chips must not share executables
        self._skey = (("device", int(getattr(device, "id", 0)))
                      if device is not None else None)
        self.params = (jax.device_put(params, device)
                       if device is not None else params)
        if self._paged:
            from . import kv_pool as _kv

            self.cache = generate.init_cache(cfg, 1, max_len,
                                             layout="paged",
                                             block_size=block_size)
            self._pool = _kv.PagedAllocator(
                self.cache["k"].shape[1], self.cache["k"].shape[2],
                self.cache["tables"].shape[1], 1)
        else:
            self._pool = None
            self.cache = generate.init_cache(cfg, 1, max_len)
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._tel = _telemetry.enabled()
        # fleet tracing: this worker's completed spans, drained onto
        # the reply/chunk messages it already sends (piggyback-capped)
        self._span_ring = _telemetry.SpanRing()

    def prefill(self, prompt, trace=None):
        """Run one prompt's admission prefill; returns ``(rows,
        logits)``: rows are host arrays ``[L, 1, n, Hkv(, hd)]`` per
        cache leaf (a paged worker's K/V rows as its pool stores them,
        ``[L, 1, n, Hkv*hd]``; int8 scale planes included) in the
        storage dtype,
        logits the fp32 ``[V]`` admission logits — exactly what
        ``DecodeServer.submit_prefilled`` expects."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        n = len(prompt)
        window = min(self.max_len, self.cfg.max_seq_len)
        if not prompt or n > window:
            raise ValueError(f"prompt length {n} outside (0, {window}]")
        t0 = time.perf_counter()
        if self._paged:
            bs = self._pool.bs
            # the decode replica's fresh-prompt rule (shared = 0):
            # bucketed suffix, floored at the block size — identical
            # executable, identical math, identical rows
            C = min(max(serving._pow2_bucket(n), bs), window)
            self._pool.ensure_rows(0, 0, n)
            tables = jnp.asarray(self._pool.tables)
            if self._device is not None:
                tables = jax.device_put(tables, self._device)
            self.cache = dict(self.cache, tables=tables)
            self._pool.dirty = False
            fn = _engine.ENGINE.get("paged_prefill", _engine.StepSpec(
                cfg=self.cfg, bucket=C, shard=self._skey))
            padded = np.zeros((1, C), np.int32)
            padded[0, :n] = prompt
            logits, self.cache = fn(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(0), jnp.asarray(n), jnp.asarray(0))
            tb = self._pool.tables[0]
            logi = np.arange(n)
            blk, off = tb[logi // bs], logi % bs
            rows = {name: np.asarray(arr)[:, blk, off][:, None]
                    for name, arr in self.cache.items() if name != "tables"}
            self._pool.free_slot(0)
        else:
            bucket = serving._pow2_bucket(n, window)
            fn = _engine.ENGINE.get("prefill", _engine.StepSpec(
                cfg=self.cfg, bucket=bucket, shard=self._skey))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = prompt
            logits, self.cache = fn(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(n), jnp.asarray(0))
            rows = {name: np.asarray(arr[:, 0:1, :n])
                    for name, arr in self.cache.items()}
        logits = np.asarray(logits, np.float32)
        if self._tel:
            _telemetry.count("fleet.prefill_jobs")
            _telemetry.observe("fleet.prefill_ms",
                               (time.perf_counter() - t0) * 1e3)
            self._span_ring.record(
                trace, "prefill_chunk[0]", t0, time.perf_counter(),
                start=0, stop=n)
        return rows, logits

    def prefill_stream(self, prompt, emit, chunk_rows=None,
                       trace=None) -> None:
        """Chunked streaming prefill (the pipelined handoff hot path):
        walk the prompt through the offset-aware chunk executables
        (``prefill_chunk@W`` / ``paged_prefill@W``) and hand each
        finished chunk's cache rows to ``emit`` WHILE the next chunk
        computes — the chunk's rows are sliced on device right after
        its dispatch, so the host fetch of chunk ``i`` overlaps the
        device compute of chunk ``i+1`` (jax async dispatch), and the
        transfer overlaps the decode replica's ticks on the far side.
        The final chunk's message carries the fp32 admission logits, so
        the receiver can graduate the slot the moment the last rows
        land (no separate done frame to lose).

        ``emit(msg)`` receives ``{"op": "chunk", "seq", "start",
        "stop", "n", "rows", ["logits"]}`` — rows are host arrays
        ``[L, 1, stop-start, Hkv(, hd)]`` per leaf, positions
        ``[start, stop)`` absolute, spans disjoint and covering
        ``[0, n)`` in order.  The chunk walk overlaps its LAST window
        (the budgeted-admission rule) instead of overrunning the
        cache/wpe bounds; overlapped rows recompute bit-identically and
        the emitted spans stay disjoint."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        n = len(prompt)
        window = min(self.max_len, self.cfg.max_seq_len)
        if not prompt or n > window:
            raise ValueError(f"prompt length {n} outside (0, {window}]")
        C = (int(chunk_rows) if chunk_rows is not None
             else _flags.stream_chunk_rows())
        W = serving._pow2_bucket(max(1, min(C, window)), window)
        if self._paged:
            # the chunk width floors at the block size, exactly like
            # the decode replica's own suffix walk
            W = min(max(W, self._pool.bs), window)
        t0 = time.perf_counter()
        if n <= W:
            # single-window prompt: the monolithic walk IS the chunk
            rows, logits = self.prefill(prompt, trace=trace)
            emit({"op": "chunk", "seq": 0, "start": 0, "stop": n,
                  "n": n, "rows": rows, "logits": logits})
            self._count_stream(rows)
            if self._tel:
                self._span_ring.record(
                    trace, "stream", t0, time.perf_counter(), chunks=1)
            return
        starts = list(range(0, n - W, W)) + [n - W]
        if self._paged:
            bs = self._pool.bs
            self._pool.ensure_rows(0, 0, n)
            tables = jnp.asarray(self._pool.tables)
            if self._device is not None:
                tables = jax.device_put(tables, self._device)
            self.cache = dict(self.cache, tables=tables)
            self._pool.dirty = False
            fn = _engine.ENGINE.get("paged_prefill", _engine.StepSpec(
                cfg=self.cfg, bucket=W, shard=self._skey))
            tb = self._pool.tables[0]
        else:
            fn = _engine.ENGINE.get("prefill_chunk", _engine.StepSpec(
                cfg=self.cfg, width=W, shard=self._skey))

        def device_rows(lo, hi):
            # lazy device-side slice of the chunk's rows, taken BEFORE
            # the next (donating) dispatch: the slice op is ordered
            # ahead of the donation on the device stream, so its output
            # buffers are independent of the donated cache
            out = {}
            for name, arr in self.cache.items():
                if name == "tables":
                    continue
                if self._paged:
                    logi = np.arange(lo, hi)
                    phys = tb[logi // bs] * bs + logi % bs
                    out[name] = _kv.take_rows(
                        arr, jnp.asarray(phys, jnp.int32))[:, None]
                else:
                    out[name] = arr[:, 0:1, lo:hi]
            return out

        pending = None            # (seq, lo, hi, device rows, t_disp)
        logits = None
        prev_stop = 0
        for j, s in enumerate(starts):
            t_disp = time.perf_counter()
            chunk = prompt[s:s + W]
            padded = np.zeros((1, W), np.int32)
            padded[0, :len(chunk)] = chunk
            logits, self.cache = fn(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(s), jnp.asarray(len(chunk)),
                jnp.asarray(0))
            lo, hi = prev_stop, min(s + W, n)
            prev_stop = hi
            if pending is not None:
                self._emit_chunk(emit, pending, n, trace=trace)
            pending = (j, lo, hi, device_rows(lo, hi), t_disp)
        self._emit_chunk(emit, pending, n,
                         logits=np.asarray(logits, np.float32),
                         trace=trace)
        if self._paged:
            self._pool.free_slot(0)
        if self._tel:
            _telemetry.count("fleet.prefill_jobs")
            _telemetry.observe("fleet.prefill_ms",
                               (time.perf_counter() - t0) * 1e3)
            self._span_ring.record(
                trace, "stream", t0, time.perf_counter(),
                chunks=len(starts))

    def _emit_chunk(self, emit, pending, n, logits=None,
                    trace=None) -> None:
        """Fetch one finished chunk's device rows (overlapping the
        in-flight next chunk) and stream it out."""
        seq, lo, hi, dev, t_disp = pending
        rows = {name: np.asarray(v) for name, v in dev.items()}
        msg = {"op": "chunk", "seq": seq, "start": lo, "stop": hi,
               "n": n, "rows": rows}
        if logits is not None:
            msg["logits"] = logits
        emit(msg)
        self._count_stream(rows)
        if self._tel:
            # dispatch → emitted: covers the chunk's device compute +
            # the row fetch that overlapped the next chunk's dispatch
            self._span_ring.record(
                trace, f"prefill_chunk[{seq}]", t_disp,
                time.perf_counter(), start=lo, stop=hi)

    def _count_stream(self, rows) -> None:
        if self._tel:
            _telemetry.count("fleet.stream_chunks")
            _telemetry.count("fleet.stream_bytes",
                             sum(a.nbytes for a in rows.values()))

    def run_once(self, timeout: float = 0.0) -> bool:
        """Consume at most one job from the endpoint (cooperative
        drive); returns whether a message was handled.  With
        ``PADDLE_TPU_STREAM_CHUNK_ROWS`` > 0 replies stream chunk by
        chunk (``{"op": "chunk", ...}``, the last one carrying the
        admission logits); 0 restores the monolithic
        ``{"rid", "rows", "logits"}`` reply."""
        msg = self.endpoint.recv(timeout)
        if msg is None:
            return False
        if isinstance(msg, dict) and msg.get("op") == "stop":
            self._stop.set()
            return True
        try:
            C = _flags.stream_chunk_rows()
            # handoff trace context: minted by the router, carried on
            # the job's header frame, stamped onto every span this
            # worker records for the job
            tr = msg.get("trace") if isinstance(msg, dict) else None
            if C > 0:
                rid = msg["rid"]
                self.prefill_stream(
                    msg["prompt"],
                    lambda m: self.endpoint.send(
                        self._with_spans(dict(m, rid=rid))),
                    chunk_rows=C, trace=tr)
            else:
                rows, logits = self.prefill(msg["prompt"], trace=tr)
                self.endpoint.send(self._with_spans(
                    {"rid": msg["rid"], "rows": rows,
                     "logits": logits}))
        except ConnectionError:
            raise                  # dead link: the caller retires it
        except Exception as e:  # noqa: BLE001 - reported to the router
            self.endpoint.send({"rid": msg.get("rid"),
                                "error": f"{type(e).__name__}: {e}"})
        return True

    def _with_spans(self, msg: dict) -> dict:
        """Drain this worker's completed spans onto an outgoing reply
        (the remote-collection piggyback; capped per message, drops
        carried so loss is accounted router-side)."""
        if self._tel:
            spans, dropped = self._span_ring.drain(
                _flags.trace_piggyback_cap())
            if spans or dropped:
                msg["spans"] = spans
                msg["span_drops"] = dropped
        return msg

    def start(self) -> None:
        """Serve jobs on a daemon thread until :meth:`close` (or a
        ``{"op": "stop"}`` frame)."""
        if self.endpoint is None:
            raise ValueError("PrefillWorker.start() needs an endpoint")
        if self._thread is not None:
            return

        def run():
            while not self._stop.is_set():
                try:
                    self.run_once(timeout=0.02)
                except ConnectionError:
                    break              # dead link: done serving it

        self._thread = threading.Thread(
            target=run, daemon=True, name=f"paddle-tpu-{self.name}")
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if self.endpoint is not None:
            self.endpoint.close()
        if self._pool is not None:
            self._pool.close()
        self.cache = None


def serve_prefill_worker(worker: PrefillWorker, host: str = "127.0.0.1",
                         port: int = 0):
    """Serve one :class:`PrefillWorker` over the socket transport (the
    cross-process deployment shape): accepts ONE router connection and
    runs the worker loop against it on a daemon thread.  Returns the
    listener (``.port`` carries the bound port; ``worker.close()`` stops
    the loop)."""
    listener = SocketTransport.listen(host, port)

    def run():
        try:
            ep = listener.accept(timeout=60.0)
        except OSError:
            return
        worker.endpoint = ep
        while not worker._stop.is_set():
            try:
                worker.run_once(timeout=0.02)
            except ConnectionError:
                break                  # router hung up: done serving it

    threading.Thread(target=run, daemon=True,
                     name=f"paddle-tpu-{worker.name}-serve").start()
    return listener


# ---------------------------------------------------------------------------
# router: admission, load balancing, health aggregation
# ---------------------------------------------------------------------------


class Router:
    """Fleet front-end over N ``DecodeServer`` replicas (+ optional
    prefill workers).

        router = fleet.Router([srv_a, srv_b], prefill=[worker])
        rid = router.submit(prompt, max_new_tokens=64)
        while router.pending():
            router.tick()
        tokens = router.result(rid)

    Requests enter a fleet-level queue (priority-ordered, TTL-shed) and
    dispatch to the least-loaded HEALTHY replica — scored on the same
    quantities the telemetry gauges sample: queue depth, then slot
    occupancy, then KV utilization (``DecodeServer.load_stats``).
    Prompts at or past ``prefill_threshold`` hand off to a prefill
    worker first; the returned rows inject via ``submit_prefilled``, so
    the decode loop never runs a long prompt's prefill.  The threshold
    COMPOSES with the replicas' in-server prefill budget
    (``PADDLE_TPU_PREFILL_BUDGET`` / ``DecodeServer(prefill_budget=)``):
    the threshold picks WHERE a prompt's prefill FLOPs run (worker vs
    replica), the budget bounds how much of a LOCAL admission a decode
    round absorbs — a below-threshold long prompt (or any prompt with
    workers absent/dead) co-schedules its prefill chunk-by-chunk
    between the replica's decode steps instead of stalling them, so
    the mixed-workload decode-gap bound holds with zero prefill
    workers attached.  A replica whose
    wedge watchdog trips is DRAINED — its queued work re-routes to
    survivors (``fleet.reroutes``) while its active slots keep decoding
    through the round-7 recovery — and :meth:`healthz` aggregates
    per-replica state (the process ``/healthz`` endpoint 503s on the
    same verdict).  ``prefill`` accepts worker-side objects
    (:class:`PrefillWorker`, auto-wired over a loopback and started) or
    ready client endpoints (e.g. ``SocketTransport.connect(...)``).

    ``close()`` shuts down the whole fleet it fronts: replicas, owned
    workers, remote workers (a stop frame), and the metrics server."""

    def __init__(self, replicas, prefill=(),
                 prefill_threshold: int | None = None,
                 tick_block: int | None = None,
                 max_queue: int | None = None,
                 metrics_port: int | None = None,
                 spares=()):
        self.replicas = list(replicas)
        if not self.replicas:
            raise ValueError("Router needs at least one decode replica")
        self._prefill_eps = []
        self._ep_windows = []      # per endpoint: worker window, or
        self._owned_workers = []   # None when unknown (raw endpoint)
        for p in prefill:
            if hasattr(p, "prefill"):          # a PrefillWorker object
                lt = LoopbackTransport()
                p.endpoint = lt.worker
                p.start()
                self._owned_workers.append(p)
                self._prefill_eps.append(lt.client)
                self._ep_windows.append(min(p.max_len,
                                            p.cfg.max_seq_len))
            else:                              # a ready client endpoint
                self._prefill_eps.append(p)
                self._ep_windows.append(None)
        self._threshold = (_flags.fleet_prefill_threshold()
                           if prefill_threshold is None
                           else int(prefill_threshold))
        self._block = (_flags.fleet_tick_block() if tick_block is None
                       else max(1, int(tick_block)))
        self._max_queue = (_flags.fleet_max_queue() if max_queue is None
                           else max(0, int(max_queue)))
        self._window = min(min(r.max_len, r.cfg.max_seq_len)
                           for r in self.replicas)
        self._default_ttl = _flags.request_ttl_s()
        self._resil = _resilience.enabled()
        self._tel = _telemetry.enabled()
        # fleet observability plane (round 20): per-track span stores —
        # the router's own spans plus rings drained from replicas and
        # workers, each bounded + drop-counted — and the aggregated
        # metrics endpoint: the router's port serves the fleet-MERGED
        # Prometheus exposition / snapshot (per-replica labels + exact
        # histogram-merge rollups), not just the process registry.
        self._trace_tracks: dict = {}
        self._t_start = time.perf_counter()
        port = (metrics_port if metrics_port is not None
                else _flags.fleet_metrics_port())
        self.metrics_server = (_telemetry.serve_metrics(
            port, render=self.render_fleet_prometheus,
            snap=self.fleet_snapshot) if port is not None else None)
        self._queue: list[int] = []            # fleet rids awaiting dispatch
        self._requests: dict[int, dict] = {}   # fleet rid -> record
        self._local: dict = {}                 # (replica, local rid) -> rid
        self._ok = [True] * len(self.replicas)
        self._next_rid = 0
        self._pf_next = 0
        self._prefilling: set[int] = set()     # rids out at a worker
        self._dead_eps: set[int] = set()       # endpoint indices gone
        # concurrent replica ticks: each replica's tick is independent
        # host scheduling around its own device dispatch, so the router
        # fans them out over a bounded thread pool (lazily created —
        # fleets of 1-2 replicas never pay a thread hop).  Router STATE
        # (queue/health/routing) stays on the caller's thread: only
        # DecodeServer.tick/tick_block runs on workers, and each replica
        # is touched by at most one worker per round.
        self._tick_workers = _flags.fleet_tick_workers()
        self._tick_pool = None
        # fleet-level admission (text/admission.py): per-tenant token
        # buckets + bounded per-class queues at the FRONT DOOR, so
        # overload sheds here instead of stacking the fleet queue on
        # top of replica queues.  The router's controller runs no
        # histogram loop of its own — every tick it absorbs the WORST
        # replica degradation rung (load_stats()["admission_rung"]) and
        # sheds by the same rung rule.  PADDLE_TPU_ADMISSION=0 builds
        # no controller: greedy routing, bit-identical to before.
        self._adm = (_admission.AdmissionController(scope="fleet")
                     if _flags.admission_enabled() else None)
        # prefix-aware routing (PADDLE_TPU_PREFIX_ROUTE): score each
        # candidate's expected prefix overlap from the radix summary its
        # load_stats ships, capped by a load-imbalance bound so affinity
        # never starves a cold replica
        self._prefix_route_on = _flags.prefix_route()
        self._route_imbalance = _flags.prefix_route_imbalance()
        # elastic fleet: registered spares + the telemetry-driven
        # scaling loop's sustain counters (PADDLE_TPU_FLEET_AUTOSCALE).
        # Removed replicas tombstone to None so every rec["replica"]
        # index stays valid for the life of the router.
        self._spares = list(spares)
        self._autoscale_on = _flags.fleet_autoscale()
        self._scale_rung = _flags.fleet_scale_rung()
        self._scale_out_ticks = _flags.fleet_scale_out_ticks()
        self._scale_in_ticks = _flags.fleet_scale_in_ticks()
        self._hot_ticks = 0
        self._idle_ticks = 0

    # -- submission ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 32,
               stop: list | None = None, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0,
               ttl_s: float | None = None, priority: int = 0,
               tenant: str | None = None) -> int:
        """Fleet-level submit: same per-request surface as
        ``DecodeServer.submit`` (sampling params, TTL, priority,
        admission tenant), one rid namespace across every replica.

        Admission control runs at THIS door: the tenant's token bucket
        (``PADDLE_TPU_TENANT_RATE``) and — when any replica's SLO
        degradation rung reaches the shed rung — lowest-class shedding,
        both retiring the request with the ``rejected`` state
        (``result`` raises ``resilience.Overloaded``).  Requests routed
        to a replica are NOT re-charged there: the fleet door is the
        one bucket."""
        vocab = next(r.cfg.vocab_size for r in self.replicas
                     if r is not None)
        prompt, stop, ttl, top_k = serving.validate_request(
            prompt, max_new_tokens, stop, temperature, top_k, top_p,
            ttl_s, window=self._window,
            vocab_size=vocab, default_ttl=self._default_ttl)
        now = time.perf_counter()
        rid = self._next_rid
        self._next_rid += 1
        req = {"prompt": prompt, "max_new": int(max_new_tokens),
               "stop": stop, "temperature": float(temperature),
               "top_k": top_k, "top_p": float(top_p),
               "ttl": ttl, "priority": int(priority),
               "tenant": tenant,
               "t_submit": now, "t_enqueue": now}
        # fleet trace context: minted HERE, carried on the request dict
        # through handoff/stream/adopt/reroute/migrate — None (no key
        # attached at all) with telemetry off, so the TELEMETRY=0 fleet
        # path is bit-identical by construction
        tr = _telemetry.mint_trace()
        if tr is not None:
            req["trace"] = tr
        rec = {"state": "queued", "req": req}
        self._requests[rid] = rec
        if self._tel:
            _telemetry.count("fleet.requests")
        if self._adm is not None:
            ok, _reason = self._adm.admit(
                tenant, priority, len(prompt) + int(max_new_tokens))
            if not ok:
                rec["state"] = "rejected"
                if self._tel:
                    _telemetry.count("fleet.requests_rejected")
                self._gauges()
                return rid
        if self._prefill_eps and len(prompt) >= self._threshold:
            self._handoff_prefill(rid, rec)
        else:
            self._queue.append(rid)
            if self._adm is not None:
                self._shed_queue_overflow()
            self._route()
        self._gauges()
        return rid

    def _shed_queue_overflow(self) -> None:
        """Bounded per-class fleet queue: while any class is over
        ``PADDLE_TPU_ADMISSION_QUEUE_CAP``, retire the controller's
        victim (lowest over-cap class, newest entry) with the
        ``rejected`` state — front-door backpressure instead of a
        fleet queue stacking on replica queues."""
        while True:
            qreqs = [self._requests[rid]["req"] for rid in self._queue]
            i = self._adm.overflow_victim(qreqs)
            if i is None:
                return
            rid = self._queue.pop(i)
            rec = self._requests[rid]
            rec["state"] = "rejected"
            self._adm.count_shed(rec["req"].get("priority", 0),
                                 "queue_full")
            if self._tel:
                _telemetry.count("fleet.requests_rejected")

    def _live_eps(self):
        return [i for i in range(len(self._prefill_eps))
                if i not in self._dead_eps]

    def _handoff_prefill(self, rid: int, rec: dict) -> None:
        """Hand one admission prefill to a worker (round-robin over the
        LIVE endpoints whose known window fits the prompt): the decode
        loop never runs this prompt's prefill, which is the
        disaggregation's whole point.  With no suitable worker — all
        dead, or every known window smaller than the prompt — the
        request falls back to the fleet queue and the owning replica
        prefills locally: slower, never stuck, never a spurious
        error."""
        n = len(rec["req"]["prompt"])

        def usable():
            return [i for i in self._live_eps()
                    if self._ep_windows[i] is None
                    or self._ep_windows[i] >= n]

        # the trace context rides the job's JSON header frame so every
        # span the worker records lands under this request's trace
        job = {"rid": rid, "prompt": rec["req"]["prompt"]}
        tr = rec["req"].get("trace")
        if tr is not None:
            job["trace"] = tr
        live = usable()
        while live:
            i = live[self._pf_next % len(live)]
            self._pf_next += 1
            try:
                self._prefill_eps[i].send(job)
            except (ConnectionError, OSError):
                self._fail_prefill_ep(i)
                live = usable()
                continue
            rec["state"] = "prefilling"
            rec["ep"] = i
            self._prefilling.add(rid)
            if self._tel:
                _telemetry.count("fleet.prefill_handoffs")
            return
        self._queue.append(rid)        # no workers left: prefill locally

    def _fail_prefill_ep(self, i: int) -> None:
        """One endpoint's transport died: every prefill out at it fails
        (the requester sees the ``error`` status, never a hang — a
        request MID-STREAM is aborted on its target replica, whose slot
        frees), and the endpoint leaves the rotation."""
        self._dead_eps.add(i)
        for rid in sorted(self._prefilling):
            rec = self._requests[rid]
            if rec.get("ep") != i:
                continue
            self._prefilling.discard(rid)
            self._abort_stream(rec, "prefill worker transport died "
                                    "mid-job")
            rec["state"] = "error"
            rec["error"] = "prefill worker transport died mid-job"
            if self._tel:
                _telemetry.count("fleet.prefill_errors")

    def _abort_stream(self, rec: dict, reason: str) -> None:
        """Tear down a half-streamed handoff on its target replica (the
        mid-stream-death rule: the request fails honestly, the claimed
        slot frees, nothing hangs)."""
        if rec.get("state") != "streaming":
            return
        i, local = rec["replica"], rec["local_rid"]
        self._local.pop((i, local), None)
        srv = self.replicas[i]
        if srv is not None:
            with contextlib.suppress(KeyError):
                srv.stream_prefilled_abort(local, reason)
        if self._tel:
            _telemetry.count("fleet.stream_aborts")

    def _stream_chunk(self, ep_i: int, msg: dict) -> None:
        """Fold one streamed prefill chunk into its decode replica —
        rows land through ``DecodeServer.stream_prefilled_rows`` (the
        per-chunk pow2 injector path) the moment they arrive, so the
        transfer overlaps the replica's decode ticks.  The FIRST chunk
        picks the replica (prefix affinity + load, same scorer as
        queued dispatch); the LAST chunk carries the admission logits
        and graduates the request to plain decoding."""
        rid = msg.get("rid")
        rec = self._requests.get(rid)
        if rec is None or rec["state"] not in ("prefilling", "streaming"):
            return                  # shed/aborted mid-stream: late rows
        if rec["state"] == "prefilling":
            i = self._pick_replica(req=rec["req"])
            if i is None:
                # every candidate is at capacity: land on the best
                # healthy replica anyway — its queue buffers the
                # streamed rows until a slot frees (the transfer has
                # to park SOMEWHERE, and the replica's host RAM is
                # where submit_prefilled would put it too)
                live = [j for j, r in enumerate(self.replicas)
                        if r is not None and self._ok[j]]
                if not live:
                    self._prefilling.discard(rid)
                    rec["state"] = "error"
                    rec["error"] = ("no healthy replica to receive "
                                    "streamed prefill rows")
                    return
                i = live[0]
            req = rec["req"]
            try:
                local = self.replicas[i].stream_prefilled_begin(
                    req["prompt"], max_new_tokens=req["max_new"],
                    stop=req.get("stop"),
                    temperature=req.get("temperature", 0.0),
                    top_k=req.get("top_k", 0),
                    top_p=req.get("top_p", 1.0),
                    ttl_s=req.get("ttl"),
                    priority=req.get("priority", 0),
                    trace=req.get("trace"))
            except ValueError as e:
                self._prefilling.discard(rid)
                rec["state"] = "error"
                rec["error"] = str(e)
                return
            rec["state"] = "streaming"
            rec["replica"] = i
            rec["local_rid"] = local
            self._local[(i, local)] = rid
            if self._tel:
                # the first chunk's replica pick IS this request's
                # routing decision (same scorer as queued dispatch)
                _telemetry.count("fleet.routed")
                self._dispatch_spans(rid, req, i)
        srv = self.replicas[rec["replica"]]
        try:
            srv.stream_prefilled_rows(
                rec["local_rid"], int(msg["start"]), int(msg["stop"]),
                msg["rows"], logits=msg.get("logits"))
        except Exception as e:  # noqa: BLE001 - surfaced on the request
            self._prefilling.discard(rid)
            self._abort_stream(rec, f"stream injection failed: {e}")
            rec["state"] = "error"
            rec["error"] = f"stream injection failed: {e}"
            return
        if msg.get("logits") is not None:
            # final chunk: the replica owns the request end to end now
            self._prefilling.discard(rid)
            rec["state"] = "dispatched"

    def _poll_prefill(self) -> None:
        for i in self._live_eps():
            ep = self._prefill_eps[i]
            while True:
                try:
                    msg = ep.recv(0.0)
                except (ConnectionError, OSError):
                    self._fail_prefill_ep(i)
                    break
                if msg is None:
                    break
                if self._tel and isinstance(msg, dict) \
                        and "spans" in msg:
                    # remote span collection: worker spans piggyback on
                    # the replies this poll already reads
                    self._absorb_spans(f"worker-{i}", msg["spans"],
                                       msg.get("span_drops", 0))
                if msg.get("op") == "chunk":
                    self._stream_chunk(i, msg)
                    continue
                rid = msg.get("rid")
                self._prefilling.discard(rid)
                rec = self._requests.get(rid)
                if rec is None or rec["state"] not in ("prefilling",
                                                       "streaming"):
                    continue
                if "error" in msg:
                    # a worker that died mid-walk reports here — a
                    # half-streamed request aborts on its replica
                    # instead of wedging its slot
                    self._abort_stream(rec, msg["error"])
                    rec["state"] = "error"
                    rec["error"] = msg["error"]
                    if self._tel:
                        _telemetry.count("fleet.prefill_errors")
                    continue
                rec["req"]["prefilled"] = (msg["rows"], msg["logits"])
                rec["state"] = "queued"
                self._queue.append(rid)

    # -- scheduling ---------------------------------------------------------

    def _expired(self, rec: dict, now: float) -> bool:
        req = rec["req"]
        ttl = req.get("ttl")
        return (ttl is not None
                and now - req.get("t_enqueue", req["t_submit"]) > ttl)

    def _shed_expired(self) -> None:
        """Fleet-queue TTL shedding (the replica rule, one level up):
        a request still waiting here — fleet-queued OR out at a prefill
        worker — past its TTL retires with the ``timeout`` status
        instead of ever reaching a replica.  A shed prefilling request's
        late reply is ignored by ``_poll_prefill`` (state check)."""
        if not self._resil or not (self._queue or self._prefilling):
            return
        now = time.perf_counter()
        kept = []
        for rid in self._queue:
            rec = self._requests[rid]
            if self._expired(rec, now):
                rec["state"] = "timeout"
                if self._tel:
                    _telemetry.count("fleet.ttl_sheds")
            else:
                kept.append(rid)
        self._queue[:] = kept
        for rid in sorted(self._prefilling):
            rec = self._requests[rid]
            if self._expired(rec, now):
                self._prefilling.discard(rid)
                # a half-streamed request frees its claimed slot too
                self._abort_stream(rec, "ttl expired mid-stream")
                rec["state"] = "timeout"
                if self._tel:
                    _telemetry.count("fleet.ttl_sheds")

    def _snapshot_load(self) -> dict:
        """ONE ``load_stats()`` read per healthy replica for the whole
        scheduling round — ``_route`` used to re-read every replica per
        QUEUED request, which multiplied the per-request host overhead
        by queue depth (and would have multiplied the radix prefix
        summaries on top).  ``_route`` keeps the snapshot honest between
        dispatches by bumping the chosen replica's queue depth."""
        return {i: r.load_stats() for i, r in enumerate(self.replicas)
                if r is not None and self._ok[i]}

    def _pick_replica(self, exclude=(), stats=None, req=None):
        """Best healthy replica with admission capacity (free slots, or
        queue headroom under ``max_queue``): prefix-affinity overlap
        leads (see :meth:`_prefix_route`), then queue depth, slot
        occupancy and KV utilization — the telemetry-gauge triple as the
        load key.  ``stats`` is the per-tick ``_snapshot_load``; absent
        (direct callers), each replica is read live as before.

        ``load_stats()`` also reports multi-tenant shape —
        ``adapters_active`` (per-adapter occupied-slot counts, when the
        replica carries an :class:`~paddle_tpu.text.adapters.AdapterPool`)
        and ``constrained_slots`` (slots decoding under a logits-mask
        constraint).  These are deliberately NOT in the score: adapter
        gathers and host-side masking cost the same tick either way, so
        affinity + load alone route correctly; the fields exist so
        operators can see which replica serves which tenant mix."""
        cands = []
        for i, r in enumerate(self.replicas):
            if r is None or not self._ok[i] or i in exclude:
                continue
            ls = (stats.get(i) if stats is not None
                  else r.load_stats())
            if ls is None:
                continue
            cap = ls["free_slots"] + max(
                0, self._max_queue - ls["queue_depth"])
            if cap <= 0:
                continue
            cands.append((i, ls))
        return self._prefix_route(req, cands)

    def _prefix_route(self, req, cands):
        """Scoring half of replica selection: per candidate, the
        expected prefix overlap (tokens) between the request's prompt
        and the replica's resident radix tree — matched by root-fanout
        fingerprint from ``load_stats()["prefix_summary"]`` — leads the
        load triple, so a tenant's traffic lands where its KV already
        lives.  Affinity credit is CAPPED: a candidate further than
        ``PADDLE_TPU_PREFIX_ROUTE_IMBALANCE`` queued requests above the
        least-loaded candidate scores zero overlap, so a hot tenant
        never starves a cold replica.  Counts ``fleet.prefix_routed``
        when affinity actually decided a dispatch.

        The ``admitting_slots`` term between depth and occupancy:
        a replica mid-(budgeted-)admission spends round budget on
        prefill chunks, so equal-depth ties prefer a replica with free
        admission headroom (all-zero when budgets are off — ordering
        unchanged)."""
        if not cands:
            return None
        prompt = (req or {}).get("prompt")
        min_q = min(ls["queue_depth"] for _, ls in cands)
        best, best_score = None, None
        for i, ls in cands:
            ov = 0
            if (self._prefix_route_on and prompt
                    and ls["queue_depth"] - min_q
                    <= self._route_imbalance):
                for run_len, fp, resident in \
                        ls.get("prefix_summary") or ():
                    if (len(prompt) >= run_len and fp
                            == _kv.prefix_fingerprint(
                                prompt[:run_len])):
                        ov = max(ov, min(resident, len(prompt)))
            score = (-ov, ls["queue_depth"],
                     ls.get("admitting_slots", 0),
                     ls["slot_occupancy"], ls["kv_utilization"], i)
            if best_score is None or score < best_score:
                best, best_score = i, score
        if best is not None and best_score[0] < 0 and self._tel:
            _telemetry.count("fleet.prefix_routed")
        return best

    def _route(self, stats=None) -> None:
        """Dispatch queued work: priority first (ties: submit order),
        each request to the best replica by prefix affinity + load;
        requests no replica can take stay fleet-queued (re-routable)."""
        if not self._queue:
            return
        if stats is None:
            stats = self._snapshot_load()
        self._queue.sort(key=lambda rid: (
            -self._requests[rid]["req"]["priority"],
            self._requests[rid]["req"]["t_submit"]))
        held = []
        for rid in self._queue:
            rec = self._requests[rid]
            rejected = {}
            while True:
                i = self._pick_replica(exclude=rejected, stats=stats,
                                       req=rec["req"])
                if i is None:
                    healthy = {j for j, r in enumerate(self.replicas)
                               if r is not None and self._ok[j]}
                    if healthy and healthy <= set(rejected):
                        # every healthy replica rejected it OUTRIGHT
                        # (window/pool too small — permanent, not a
                        # capacity wait): error beats an eternal queue
                        rec["state"] = "error"
                        rec["error"] = "; ".join(
                            sorted(set(rejected.values())))
                        if self._tel:
                            _telemetry.count("fleet.route_errors")
                    else:
                        held.append(rid)
                    break
                self._migrate_chains(rec["req"], i)
                try:
                    local = self.replicas[i].adopt_request(rec["req"])
                except ValueError as e:
                    rejected[i] = str(e)
                    continue
                rec["state"] = "dispatched"
                rec["replica"] = i
                rec["local_rid"] = local
                self._local[(i, local)] = rid
                if i in stats:
                    # keep the snapshot honest for the REST of this
                    # round: the adopted request consumes a free slot
                    # if one was open, else sits on i's queue — the
                    # mirror of the ``cap`` admission arithmetic above
                    if stats[i]["free_slots"] > 0:
                        stats[i]["free_slots"] -= 1
                    else:
                        stats[i]["queue_depth"] += 1
                if self._tel:
                    _telemetry.count("fleet.routed")
                    self._dispatch_spans(rid, rec["req"], i)
                break
        self._queue[:] = held

    def _check_health(self) -> None:
        for i, r in enumerate(self.replicas):
            if r is None:
                continue
            ok = not r.wedged
            if self._ok[i] and not ok:
                self._ok[i] = False
                self._drain_replica(i)
            elif ok and not self._ok[i]:
                self._ok[i] = True
                if self._tel:
                    _telemetry.count("fleet.replica_recoveries")

    def _drain_replica(self, i: int) -> None:
        """A replica's wedge watchdog tripped: pull its QUEUED work back
        into the fleet queue (front — it has waited already) so healthy
        replicas pick it up; its active slots stay, the round-7 recovery
        replays their steps bit-exactly."""
        if self._tel:
            _telemetry.count("fleet.drains")
        # drain ONLY the rids this router owns: a request submitted
        # directly to the replica stays on its queue (only the direct
        # submitter holds its local rid — moving it would strand them)
        mine = {lr for (ri, lr) in self._local if ri == i}
        reqs = self.replicas[i].drain_queue(mine)
        front = []
        for req in reqs:
            rid = self._local.pop((i, req["rid"]), None)
            if rid is None:
                continue        # unreachable given the rid filter
            rec = self._requests[rid]
            if req.get("stream"):
                # a still-queued streamed handoff cannot re-route: its
                # chunks flow to THIS replica's stream plumbing.  Fail
                # it honestly (the worker's late chunks drop on the
                # state check) instead of stranding it elsewhere
                self._prefilling.discard(rid)
                rec["state"] = "error"
                rec["error"] = "stream target replica drained mid-handoff"
                rec.pop("replica", None)
                rec.pop("local_rid", None)
                if self._tel:
                    _telemetry.count("fleet.stream_aborts")
                continue
            r = dict(req)
            r.pop("rid", None)  # the local rid died with the drain
            rec["req"] = r
            rec["state"] = "queued"
            rec.pop("replica", None)
            rec.pop("local_rid", None)
            front.append(rid)
            # the trace context rides the request dict through the
            # reroute; the marker span keeps the hop visible
            tr = r.get("trace")
            if tr:
                now = time.perf_counter()
                self._track("router").record(tr, "reroute", now, now,
                                             rid=rid, src=i)
        if front:
            self._queue[:0] = front
            if self._tel:
                _telemetry.count("fleet.reroutes", len(front))

    # -- elastic fleet ------------------------------------------------------

    def _migrate_chains(self, req, dest_i: int) -> None:
        """Cross-replica spilled-chain migration: before ``dest_i``
        adopts a request, any OTHER replica holding a host-RAM spilled
        prefix chain of this prompt ships it over — the entries
        roundtrip through the raw wire codec (the same dtype-tagged
        header + buffer frames a socket fleet moves KV with; loopback
        fleets exercise the exact encode path), land in the
        destination pool's spill store, and restore bit-identically
        through ITS ``inject_rows`` buckets at admission.  The source
        forgets the chain (a move, not a copy): prefix-aware routing
        already steers the tenant here, so the chain follows the
        traffic.  Cold path — runs only when a source actually holds a
        matching chain (``kv_pool.chain_migrations``)."""
        prompt = req.get("prompt")
        dest = self.replicas[dest_i]
        pool = getattr(dest, "_pool", None)
        if not prompt or pool is None \
                or not hasattr(pool, "migrate_in"):
            return
        for j, r in enumerate(self.replicas):
            if j == dest_i or r is None:
                continue
            src = getattr(r, "_pool", None)
            if src is None or not hasattr(src, "migrate_out"):
                continue
            entries = src.migrate_out(prompt)
            if not entries:
                continue
            t0m = time.perf_counter()
            hdr, arrays = _encode_msg(entries)
            entries = _decode_msg(
                hdr, [bytearray(a.reshape(-1).view(np.uint8))
                      for a in arrays])
            pool.migrate_in(entries)
            # traced requests keep their chain moves on the timeline
            tr = req.get("trace")
            if tr:
                self._track("router").record(
                    tr, "migrate", t0m, time.perf_counter(),
                    src=j, dest=dest_i)

    def add_replica(self, srv) -> int:
        """Attach a decode replica LIVE: it joins the routing candidate
        set on the next scheduling round (in-flight requests are
        untouched).  The fleet window tightens if the newcomer's is
        smaller — already-queued longer prompts are rejected by it at
        adoption and re-route, never wedge.  Returns the replica
        index."""
        self.replicas.append(srv)
        self._ok.append(True)
        self._window = min(self._window,
                           min(srv.max_len, srv.cfg.max_seq_len))
        if self._tel:
            _telemetry.count("fleet.replica_adds")
        self._gauges()
        return len(self.replicas) - 1

    def remove_replica(self, i: int):
        """Detach replica ``i`` LIVE: its queued router-owned work
        re-routes to the survivors (the wedge/drain machinery — the
        survivors' outputs are bit-identical to an undisturbed run,
        their slots never observe the topology change), a half-streamed
        handoff targeting it fails honestly, and its ACTIVE slots tick
        to completion here with results materialized into the fleet
        records before the handle goes away.  The slot tombstones to
        ``None`` so every ``rec["replica"]`` index stays valid for the
        router's lifetime.  Returns the detached server (the caller
        owns it again — park it as a spare or ``close()`` it)."""
        srv = self.replicas[i]
        if srv is None:
            raise KeyError(f"replica {i} was already removed")
        if sum(1 for r in self.replicas if r is not None) <= 1:
            raise ValueError("cannot remove the last replica")
        self._drain_replica(i)
        # a stream mid-flight to this replica would hold its claimed
        # slot open forever (the worker keeps computing, but its chunks
        # drop on the state check): abort it so pending() can fall
        for rid in sorted(self._prefilling):
            rec = self._requests[rid]
            if (rec.get("state") == "streaming"
                    and rec.get("replica") == i):
                self._prefilling.discard(rid)
                self._abort_stream(rec, "replica removed mid-stream")
                rec["state"] = "error"
                rec["error"] = "replica removed mid-stream"
        while srv.pending():
            self._tick_replica(srv)
        for (ri, local), rid in list(self._local.items()):
            if ri != i:
                continue
            rec = self._requests[rid]
            try:
                rec["result"] = srv.result(local)
                rec["state"] = "done"
            except Exception as e:  # noqa: BLE001 - surfaced on result
                rec["state"] = "error"
                rec["error"] = str(e)
            del self._local[(ri, local)]
        if self._tel and hasattr(srv, "drain_spans"):
            # last collection before the handle leaves the fleet — a
            # departing replica's spans must not vanish with it
            spans, drops = srv.drain_spans()
            self._absorb_spans(f"replica-{i}", spans, drops)
        self.replicas[i] = None
        self._ok[i] = False
        self._window = min(min(r.max_len, r.cfg.max_seq_len)
                           for r in self.replicas if r is not None)
        if self._tel:
            _telemetry.count("fleet.replica_removes")
        self._route()
        self._gauges()
        return srv

    def register_spare(self, srv) -> None:
        """Park a warm replica for the autoscale loop: ``_scale_out``
        attaches spares in registration order; ``_scale_in`` returns
        drained replicas to the pool.  Spares cost device memory but no
        ticks — the price of scale-out latency measured in one
        scheduling round instead of a model load."""
        self._spares.append(srv)

    def _autoscale(self, stats) -> bool:
        """Telemetry-driven scaling loop (``PADDLE_TPU_FLEET_AUTOSCALE``):
        the fleet scales OUT to a registered spare after the worst
        healthy replica's SLO degradation rung has held at or above
        ``PADDLE_TPU_FLEET_SCALE_RUNG`` for ``_SCALE_OUT_TICKS``
        consecutive rounds, and scales IN (drain + re-route, survivors
        bit-identical) after ``_SCALE_IN_TICKS`` rounds with zero
        queued, streaming, or occupied-slot work anywhere.  Sustain
        windows debounce both directions — one hot histogram window
        never flaps the topology.  Returns True when the topology
        changed (the caller refreshes its load snapshot)."""
        if stats is None:
            stats = self._snapshot_load()
        rungs = [ls.get("admission_rung", 0) for ls in stats.values()]
        hot = bool(rungs) and max(rungs) >= self._scale_rung
        busy = (bool(self._queue) or bool(self._prefilling)
                or any(ls["queue_depth"] > 0 or ls["slot_occupancy"] > 0
                       for ls in stats.values()))
        self._hot_ticks = self._hot_ticks + 1 if hot else 0
        self._idle_ticks = 0 if busy else self._idle_ticks + 1
        if self._hot_ticks >= self._scale_out_ticks and self._spares:
            self._scale_out()
            return True
        if (self._idle_ticks >= self._scale_in_ticks
                and sum(1 for r in self.replicas
                        if r is not None) > 1):
            self._scale_in()
            return True
        return False

    def _scale_out(self) -> None:
        """Sustained overload verdict: the oldest registered spare
        joins the fleet (``fleet.scale_outs``)."""
        self.add_replica(self._spares.pop(0))
        self._hot_ticks = 0
        if self._tel:
            _telemetry.count("fleet.scale_outs")

    def _scale_in(self) -> None:
        """Sustained idle verdict: the highest-index live replica
        drains out of the fleet and returns to the spare pool
        (``fleet.scale_ins``)."""
        live = [j for j, r in enumerate(self.replicas)
                if r is not None]
        self._spares.append(self.remove_replica(live[-1]))
        self._idle_ticks = 0
        if self._tel:
            _telemetry.count("fleet.scale_ins")

    def _tick_replica(self, r) -> None:
        if self._block > 1:
            r.tick_block(self._block)
        else:
            r.tick()

    def tick(self) -> None:
        """One fleet scheduling round: fold in finished prefills, health
        check (drain + re-route on a wedge flip), TTL shed, dispatch,
        then tick every replica with pending work — wedged ones
        included, since their recovery needs ticks.

        Replica ticks run CONCURRENTLY over a bounded thread pool
        (``PADDLE_TPU_FLEET_TICK_WORKERS``) — a sequential loop was fine
        for 2 replicas, not 16 waiting on each other's device fetches.
        The round is still a barrier: every replica's tick completes (or
        raises) before the post-round health check, so the wedge-drain
        semantics are EXACTLY the sequential loop's — a wedge verdict
        raised on a worker thread is observed by ``_check_health`` on
        this thread after the join, and the drain/re-route runs here,
        single-threaded.  The first replica exception propagates to the
        caller after all ticks joined (no replica is left mid-round)."""
        self._poll_prefill()
        self._check_health()
        self._shed_expired()
        # ONE load_stats snapshot feeds this round's backpressure fold
        # AND every routing decision (the per-queued-request re-read is
        # gone); skipped when nothing needs it
        stats = (self._snapshot_load()
                 if self._queue or self._adm is not None
                 or self._autoscale_on else None)
        self._absorb_backpressure(stats)
        if self._autoscale_on and self._autoscale(stats):
            stats = self._snapshot_load()   # topology changed
        self._route(stats)
        pend = [r for r in self.replicas
                if r is not None and r.pending()]
        if len(pend) <= 1 or self._tick_workers <= 1:
            for r in pend:
                self._tick_replica(r)
        else:
            if self._tick_pool is None:
                self._tick_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=min(len(self.replicas),
                                    self._tick_workers),
                    thread_name_prefix="fleet-tick")
            errs = []
            for f in [self._tick_pool.submit(self._tick_replica, r)
                      for r in pend]:
                try:
                    f.result()
                except Exception as e:  # noqa: BLE001 - re-raised below
                    errs.append(e)
            if errs:
                raise errs[0]
        self._check_health()
        self._harvest_spans()
        self._gauges()

    def _absorb_backpressure(self, stats=None) -> None:
        """Fold the replicas' SLO verdicts into the front door: the
        router's controller adopts the WORST healthy replica's
        degradation rung (``load_stats()["admission_rung"]``), so when
        any replica degrades to the shed rung, new lowest-class
        submissions reject HERE — before queueing, before routing —
        and recovery tracks the replicas' own ladders exactly.
        ``stats`` is the tick's shared ``_snapshot_load``."""
        if self._adm is None:
            return
        if stats is None:
            stats = self._snapshot_load()
        rungs = [ls.get("admission_rung", 0) for ls in stats.values()]
        self._adm.absorb_fleet_rung(max(rungs) if rungs else 0)

    def pending(self) -> bool:
        return (bool(self._queue) or bool(self._prefilling)
                or any(r.pending() for r in self.replicas
                       if r is not None))

    # -- results ------------------------------------------------------------

    def status(self, rid: int) -> str:
        """``queued`` | ``prefilling`` | ``timeout`` | ``rejected`` |
        ``error`` at the fleet level; once dispatched, the owning
        replica's status; ``ok`` for a result materialized by
        :meth:`remove_replica` after its replica left the fleet."""
        rec = self._requests[rid]
        if rec["state"] == "dispatched":
            return self.replicas[rec["replica"]].status(rec["local_rid"])
        if rec["state"] == "done":
            return "ok"
        return rec["state"]

    def result(self, rid: int):
        rec = self._requests[rid]
        state = rec["state"]
        if state == "done":
            # materialized by remove_replica before its replica left
            return rec["result"]
        if state == "timeout":
            raise _resilience.DeadlineExceeded(
                f"request {rid} was shed at the router: still queued "
                f"past its ttl")
        if state == "rejected":
            raise _resilience.Overloaded(
                f"request {rid} was rejected at the fleet door "
                f"(rate limit, queue bound, or overload shed) — it "
                f"never queued; back off and resubmit")
        if state == "error":
            raise RuntimeError(
                f"request {rid} failed: {rec.get('error')}")
        if state != "dispatched":
            raise KeyError(f"request {rid} is still {state}")
        return self.replicas[rec["replica"]].result(rec["local_rid"])

    # -- health + telemetry -------------------------------------------------

    def healthz(self) -> dict:
        """Aggregated fleet health: ``ok`` iff every replica's wedge
        watchdog is clear, plus each replica's live load stats — the
        fleet twin of the process ``GET /healthz`` (which 503s on the
        same wedge verdict via the shared telemetry state)."""
        reps = []
        for i, r in enumerate(self.replicas):
            if r is None:
                continue
            ls = r.load_stats()
            reps.append(dict(ls, ok=not ls["wedged"]))
        return {
            "ok": all(rp["ok"] for rp in reps),
            "replicas": reps,
            "queue_depth": len(self._queue),
            "prefill_workers": len(self._prefill_eps),
            "prefill_outstanding": len(self._prefilling),
            # admission verdict at the fleet door (None = controller
            # off): the rung the front door currently sheds by, plus
            # the shared admission.* counter/gauge snapshot
            "admission": (None if self._adm is None
                          else self._adm.stats()),
        }

    # -- fleet tracing: collection + assembly -------------------------------

    def _track(self, name: str) -> _telemetry.SpanRing:
        """The named span track (lazily created): ``router`` for spans
        this process records, ``replica-N``/``worker-N`` for rings
        collected from the fleet — each bounded + drop-counted."""
        ring = self._trace_tracks.get(name)
        if ring is None:
            ring = self._trace_tracks[name] = _telemetry.SpanRing()
        return ring

    def _absorb_spans(self, track: str, spans, dropped=0) -> None:
        """Fold a remote ring's drained spans + drop count into the
        named track (drops also surface on ``fleet.trace_drops``)."""
        ring = self._track(track)
        for s in spans or ():
            if isinstance(s, dict):
                ring.push(s)
        if dropped:
            ring.add_drops(int(dropped))
            _telemetry.count("fleet.trace_drops", int(dropped))

    def _dispatch_spans(self, rid: int, req: dict, replica: int) -> None:
        """The dispatch decision on the trace: the fleet-queue wait and
        a zero-width route marker naming the chosen replica."""
        tr = req.get("trace")
        if not tr:
            return
        now = time.perf_counter()
        ring = self._track("router")
        ring.record(tr, "queue_wait",
                    req.get("t_enqueue", req.get("t_submit", now)), now,
                    rid=rid)
        ring.record(tr, "route", now, now, rid=rid, replica=replica)

    def _harvest_spans(self) -> None:
        """One collection round: drain every live replica's span ring
        (the piggyback the ``load_stats(include_spans=True)`` API rides)
        into its per-replica track.  Worker spans arrive separately on
        the replies ``_poll_prefill`` already reads."""
        if not self._tel:
            return
        for i, r in enumerate(self.replicas):
            if r is None or not hasattr(r, "drain_spans"):
                continue
            spans, dropped = r.drain_spans()
            if spans or dropped:
                self._absorb_spans(f"replica-{i}", spans, dropped)

    def fleet_trace(self) -> dict:
        """``{track: [span, ...]}`` — a fresh collection round plus a
        non-destructive snapshot of every span track (``router``,
        ``replica-N``, ``worker-N``).  Spans are wall-clock stamped, so
        tracks from different processes share one timeline."""
        self._harvest_spans()
        return {nm: ring.spans()
                for nm, ring in sorted(self._trace_tracks.items())}

    def dump_fleet_trace(self, path: str) -> str:
        """Assemble ONE Perfetto-loadable timeline for the whole fleet:
        a process track per span source (router / replica-N / worker-N,
        one tid row per request) beside the process-global telemetry
        ring (request/compile events + HBM counter samples) shifted
        from the perf clock onto the wall clock.  Every request that
        crossed the fleet shows its full waterfall — queue_wait/route at
        the router, prefill_chunk[i]/stream at the worker,
        inject/decode/spec_round/retire at the replica — under a single
        ``trace_id``."""
        tracks = self.fleet_trace()
        evs = []
        pid = 1
        for nm, spans in tracks.items():
            evs.extend(_telemetry.spans_to_chrome(
                spans, pid=pid, name=f"fleet.{nm}"))
            pid += 1
        evs.extend(_telemetry.chrome_events(
            pid=0, shift=time.time() - time.perf_counter()))
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f)
        return path

    # -- fleet metrics aggregation ------------------------------------------

    def fleet_snapshot(self) -> dict:
        """The aggregated metrics view the router's ``/snapshot``
        serves: each replica's per-server histogram states + counters +
        live load, fleet rollups computed by EXACT log-bucket histogram
        merge (every histogram shares the fixed bucket ladder, so the
        fleet p99 equals the p99 of the concatenated samples to within
        one bucket width — not an average of quantiles), and the span
        tracks' collection accounting."""
        reps: dict = {}
        merged: dict = {}
        counters: dict = {}
        for i, r in enumerate(self.replicas):
            if r is None:
                continue
            snap = (r.local_snapshot()
                    if hasattr(r, "local_snapshot")
                    else {"histograms": {}, "counters": {}})
            summaries = {}
            for name, stt in snap["histograms"].items():
                h = merged.get(name)
                if h is None:
                    h = merged[name] = _telemetry.Histogram(
                        f"fleet.{name}")
                h.merge(stt)
                one = _telemetry.Histogram(name)
                one.merge(stt)
                summaries[name] = one.summary()
            reps[str(i)] = {
                "histograms": snap["histograms"],
                "summaries": summaries,   # pre-digested for fleet_top
                "counters": snap["counters"],
                "load": r.load_stats(),
                "healthy": bool(self._ok[i]),
            }
            for name, c in snap["counters"].items():
                counters[name] = counters.get(name, 0) + c
        uptime = max(time.perf_counter() - self._t_start, 1e-9)
        toks = counters.get("serving.tokens_generated", 0)
        ttft = merged.get("serving.ttft_ms")
        tpot = merged.get("serving.tpot_ms")
        return {
            "replicas": reps,
            "fleet": {
                "replicas": sum(1 for r in self.replicas
                                if r is not None),
                "healthy_replicas": sum(self._ok),
                "queue_depth": len(self._queue),
                "prefill_outstanding": len(self._prefilling),
                "uptime_s": round(uptime, 3),
                "tokens_generated": toks,
                "tok_s": round(toks / uptime, 3),
                "requests_completed": counters.get(
                    "serving.requests_completed", 0),
                "ttft_p99_ms": (round(ttft.quantile(0.99), 6)
                                if ttft is not None else 0.0),
                "tpot_p99_ms": (round(tpot.quantile(0.99), 6)
                                if tpot is not None else 0.0),
                "histograms": {name: h.summary()
                               for name, h in sorted(merged.items())},
            },
            "trace": {nm: {"spans": len(ring),
                           "dropped": ring.dropped}
                      for nm, ring in sorted(
                          self._trace_tracks.items())},
        }

    @staticmethod
    def _render_hist_lines(out: list, name: str, h, label: str) -> None:
        pn = ("paddle_tpu_fleet_"
              + name.replace(".", "_").replace("-", "_"))
        for ub, cum in h.buckets():
            le = "+Inf" if ub == float("inf") else repr(ub)
            out.append(f'{pn}_bucket{{{label},le="{le}"}} {cum}')
        s = h.summary()
        out.append(f'{pn}_sum{{{label}}} {s["sum"]}')
        out.append(f'{pn}_count{{{label}}} {s["count"]}')

    def render_fleet_prometheus(self) -> str:
        """One Prometheus exposition for the whole fleet: the process
        registry first (unchanged families), then every replica's
        per-server histograms re-labeled ``{replica="i"}`` under
        ``paddle_tpu_fleet_*`` family names (a distinct family, so the
        process-level TYPE lines never duplicate), then the fleet
        rollups — merged by exact bucket addition, never quantile
        averaging."""
        snap = self.fleet_snapshot()
        out = [_telemetry.render_prometheus().rstrip("\n")]
        for i in sorted(snap["replicas"], key=int):
            rep = snap["replicas"][i]
            for name, stt in rep["histograms"].items():
                h = _telemetry.Histogram(name)
                h.merge(stt)
                self._render_hist_lines(out, name, h,
                                        f'replica="{i}"')
            for name, c in rep["counters"].items():
                pn = ("paddle_tpu_fleet_"
                      + name.replace(".", "_").replace("-", "_")
                      + "_total")
                out.append(f'{pn}{{replica="{i}"}} {c}')
        fl = snap["fleet"]
        for k in ("replicas", "healthy_replicas", "queue_depth",
                  "prefill_outstanding", "tokens_generated", "tok_s",
                  "ttft_p99_ms", "tpot_p99_ms"):
            out.append(f"paddle_tpu_fleet_{k} {fl[k]}")
        return "\n".join(out) + "\n"

    def _gauges(self) -> None:
        if not self._tel:
            return
        _telemetry.set_gauge(
            "fleet.replicas",
            sum(1 for r in self.replicas if r is not None))
        _telemetry.set_gauge("fleet.healthy_replicas", sum(self._ok))
        _telemetry.set_gauge("fleet.queue_depth", len(self._queue))
        _telemetry.set_gauge("fleet.prefill_outstanding",
                             len(self._prefilling))
        if self._adm is not None:
            _telemetry.set_gauge("admission.fleet_rung", self._adm.rung)

    def close(self) -> None:
        """Shut the fleet down: stop frames to remote workers, owned
        workers closed, every replica closed (unfinished work is
        abandoned per ``DecodeServer.close``), metrics server joined."""
        for ep in self._prefill_eps:
            with contextlib.suppress(Exception):
                ep.send({"op": "stop"})
            with contextlib.suppress(Exception):
                ep.close()
        for w in self._owned_workers:
            with contextlib.suppress(Exception):
                w.close()
        if self._tick_pool is not None:
            self._tick_pool.shutdown(wait=True)
            self._tick_pool = None
        for r in list(self.replicas) + list(self._spares):
            if r is None:
                continue
            with contextlib.suppress(Exception):
                r.close()
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
