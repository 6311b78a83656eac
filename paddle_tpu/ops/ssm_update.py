"""The decode step's recurrent-state update where the state is stored.

One Pallas kernel advances one layer of the serving cache's ``ssm`` leaf
``[L, slots, H, P, N]`` (float32, ``text/ssm.py``) by one token for the
slots that decode in this step, and reads the mixer's output off the new
state:

    S = exp(dt A) S + (dt x) (outer) B          y = S C

It is handed the WHOLE leaf in HBM, aliased to its output, the layer's
number, and the step's ``live`` and ``pos``.  A list of the decoding slots,
compacted on the device from ``live`` with its count, is prefetched; grid
cell j visits slot ``slots[j]`` and a cell past the count does nothing and
fetches nothing.  A visited slot's state is copied in a block of heads at
a time (about 1 MB: both halves of the input and of the output buffer stay
inside the default scoped VMEM), updated, and copied back where it was;
the next block's copy, the next slot's first included, flies meanwhile.
A slot at ``pos == 0`` starts from zero and is not read at all.  A slot
that does not decode is neither read nor written: it keeps its state bit
for bit by construction, as does every other layer of the leaf.

The small per-slot operands come laid out for the kernel: a head's
``dt x`` is a column ([P, 1], then lanes are broadcast) and its output one,
so both are [slots, blocks, P, heads a block]; the wrapper transposes them
in XLA, a megabyte each.  The arithmetic is float32 on the vector unit:
the state's dtype, as ``SSMConfig.state_dtype`` has it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _pallas

_INTERPRET = False       # tests flip this to run the kernel on the CPU
_BLOCK_BYTES = 1 << 20   # of one slot's state a copy moves, at most


def _heads_a_block(H: int, G: int, P: int, N: int) -> int | None:
    """Heads of one slot-layer a block holds: the most that stay inside
    one B/C group and ``_BLOCK_BYTES``; None where no head fits."""
    fit = [h for h in range(1, H // G + 1)
           if (H // G) % h == 0 and h * P * N * 4 <= _BLOCK_BYTES]
    return max(fit) if fit else None


def supported(leaf_shape, dtype, n_groups: int) -> bool:
    """Static gate: a float32 leaf [L, slots, H, P, N] whose [P, N] head
    is whole (8, 128) tiles and small enough to copy a head at a time."""
    if len(leaf_shape) != 5 or jnp.dtype(dtype) != jnp.float32:
        return False
    _, _, H, P, N = leaf_shape
    return (H % n_groups == 0 and P % 8 == 0 and N % 128 == 0
            and _heads_a_block(H, n_groups, P, N) is not None)


def available(leaf_shape, dtype, n_groups: int) -> bool:
    """:func:`supported` + a backend that runs the kernel (a TPU, or
    interpret mode when a test flipped ``_INTERPRET``) on one device: the
    trace-time routing check ``ssm.mixer_step_pooled`` consults."""
    return (supported(leaf_shape, dtype, n_groups)
            and (_INTERPRET or _pallas.on_tpu())
            and _pallas.partition() is None)


def decoding_slots(live):
    """``live`` [slots] bool -> (the decoding slots' numbers first, in
    order, the rest repeating the last of them so that a cell past the
    count names the block the cell before it held; their count [1])."""
    B = live.shape[0]
    count = jnp.sum(live, dtype=jnp.int32)
    (idx,) = jnp.nonzero(live, size=B, fill_value=0)
    idx = idx.astype(jnp.int32)
    last = idx[jnp.maximum(count - 1, 0)]
    return jnp.where(jnp.arange(B) < count, idx, last), count.reshape(1)


def state_update(leaf, layer, live, pos, dtx, decay, b, c):
    """Advance layer ``layer`` (int32 scalar) of ``leaf`` [L, slots, H, P,
    N] for the slots ``live`` [slots] names.  Per slot: ``dtx`` [slots, H,
    P] (dt x), ``decay`` [slots, H] (exp(dt A)), ``b`` / ``c`` [slots, G,
    N]; ``pos`` [slots] (0: the slot starts from zero).  All float32.
    Returns (y [slots, H, P] with zeros for the slots that do not decode,
    the leaf).  The caller checked :func:`available`."""
    return _call(leaf, layer, live, pos, dtx, decay, b, c,
                 interpret=_INTERPRET)


# jitted, so that a step that calls it once a layer in a Python loop traces
# the kernel's unrolled heads once and not once a layer (1.3 s a trace on
# the chip's host: set-up time of every launch, compile cache or not)
@functools.partial(jax.jit, static_argnames="interpret")
def _call(leaf, layer, live, pos, dtx, decay, b, c, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, B, H, P, N = leaf.shape
    G = b.shape[1]
    Hb = _heads_a_block(H, G, P, N)
    nb = H // Hb            # blocks of a slot-layer
    per_group = nb // G     # of which one B/C group's heads fill this many

    def kernel(slots_ref, pos_ref, count_ref, layer_ref, dtx_ref, dec_ref,
               b_ref, c_ref, s_hbm, y_ref, s_out, ibuf, obuf, isem, osem):
        j = pl.program_id(0)
        count, li = count_ref[0], layer_ref[0]

        def fetch(slot, blk, half):
            """Start the copy in of block ``blk`` of ``slot`` (zeros, and
            no copy, where the slot starts a sequence)."""
            @pl.when(pos_ref[slot] == 0)
            def _zero():
                ibuf[half] = jnp.zeros(ibuf.shape[1:], ibuf.dtype)

            @pl.when(pos_ref[slot] != 0)
            def _copy():
                pltpu.make_async_copy(
                    s_hbm.at[li, slot, pl.ds(blk * Hb, Hb)], ibuf.at[half],
                    isem.at[half]).start()

        def written(half):
            # a wait takes its size from the descriptor, not its place
            pltpu.make_async_copy(obuf.at[half], s_out.at[0, 0, pl.ds(0, Hb)],
                                  osem.at[half]).wait()

        @pl.when(j < count)
        def _visit():
            slot = slots_ref[j]
            lane = jax.lax.broadcasted_iota(jnp.int32, (P, Hb), 1)

            @pl.when(j == 0)
            def _first():
                fetch(slot, 0, 0)

            def block(blk, carry):
                w = j * nb + blk        # the block's number in the step
                half = jax.lax.rem(w, 2)

                @pl.when(blk + 1 < nb)
                def _next_block():
                    fetch(slot, blk + 1, 1 - half)

                @pl.when((blk + 1 == nb) & (j + 1 < count))
                def _next_slot():
                    fetch(slots_ref[jnp.minimum(j + 1, B - 1)], 0, 1 - half)

                @pl.when(pos_ref[slot] != 0)
                def _arrived():
                    pltpu.make_async_copy(
                        s_hbm.at[0, 0, pl.ds(0, Hb)], ibuf.at[half],
                        isem.at[half]).wait()

                @pl.when(w >= 2)
                def _free():
                    written(half)

                g = jax.lax.div(blk, per_group)
                bg = b_ref[0, pl.ds(g, 1), :]               # [1, N]
                cg = c_ref[0, pl.ds(g, 1), :]
                dtx_b, dec_b = dtx_ref[0, blk], dec_ref[0, blk]
                y = jnp.zeros((P, Hb), jnp.float32)
                for h in range(Hb):
                    new = (ibuf[half, h] * dec_b[:, h:h + 1]
                           + dtx_b[:, h:h + 1] * bg)        # [P, N]
                    obuf[half, h] = new
                    y = jnp.where(lane == h, jnp.sum(
                        new * cg, axis=1, keepdims=True), y)
                y_ref[0, blk] = y
                pltpu.make_async_copy(
                    obuf.at[half], s_out.at[li, slot, pl.ds(blk * Hb, Hb)],
                    osem.at[half]).start()
                return carry

            jax.lax.fori_loop(0, nb, block, 0)

            @pl.when(j == count - 1)
            def _drain():
                # the step's last two blocks' copies out are still flying
                @pl.when(count * nb >= 2)
                def _before_last():
                    written(jax.lax.rem(count * nb, 2))

                written(jax.lax.rem(count * nb - 1, 2))

    slots, count = decoding_slots(live)

    def small(*dims):
        """A per-slot operand's block: the slot this cell visits."""
        return pl.BlockSpec((1,) + dims, lambda j, slots_ref, *_: (
            slots_ref[j],) + (0,) * len(dims))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[small(nb, P, Hb), small(nb, 1, Hb), small(G, N),
                  small(G, N), hbm],
        out_specs=[small(nb, P, Hb), hbm],
        scratch_shapes=[pltpu.VMEM((2, Hb, P, N), jnp.float32),
                        pltpu.VMEM((2, Hb, P, N), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    # a head's dt x as a column: [slots, blocks, P, heads a block]
    cols = functools.partial(jnp.swapaxes, axis1=2, axis2=3)
    y, leaf = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nb, P, Hb), jnp.float32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # operand 8 (after the four prefetched) is the leaf: written where
        # it is stored
        input_output_aliases={8: 1},
        interpret=interpret,
        name="ssm_state_update",
    )(slots, pos.astype(jnp.int32), count,
      jnp.asarray(layer, jnp.int32).reshape(1),
      cols(dtx.reshape(B, nb, Hb, P)), decay.reshape(B, nb, 1, Hb),
      b, c, leaf)
    # the cells that ran wrote their own block of y; the rest of it is
    # whatever the buffer held
    y = cols(y).reshape(B, H, P)
    return jnp.where(live[:, None, None], y, 0.0), leaf
