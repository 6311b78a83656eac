"""A model family: the one place in the benchmark that knows an
architecture.

A configuration file states its family (``"family": "gpt"``), and
``common.family(config)`` imports ``benchmarks/families/<family>.py``.  The
harness (``serve.py``, ``generators/``, ``readers/``) asks that module for
everything that depends on the architecture and knows nothing of it
otherwise: no key of the file's ``model`` block, no class of the program,
no reference.  A new family is this module, its plain reference beside it
and a configuration file; no file that is here needs an edit.

What a family module gives.  ``config`` is the configuration file as
loaded (with its ``rehearse`` block laid on top in a rehearsal); ``sizes``
is what the family's own ``sizes(config)`` returned.

``sizes(config) -> dict``
    The published sizes under whatever names the family likes, and three
    that the generators and mixes read: ``T`` (positions a sequence may
    have), ``V_published`` (token ids the traffic draws from) and ``V``
    (rows the program holds, the padding included).  Sizes the program
    cannot take are an error here (``SystemExit``), never rounded.

``weights(config, seed) -> (cfg, params)``
    The program's configuration object and the weights from ``seed``
    (already cut to what a jax key takes), made on the device in one jitted
    call, in the configuration's ``dtype``.  A ``role: serve`` cell only.

``server(config, cfg, params) -> srv``
    The server a ``role: serve`` cell drives: what the file's
    ``entry_point.call`` names (``common.entry_point``), built with
    ``entry_point.args``.  ``serve.Driver`` needs ``submit(prompt,
    max_new_tokens=)``, ``tick()``, ``pending()``, ``status(rid)``,
    ``result(rid)``, ``warmup(prompt_lens=)`` and ``close()`` of it.

``served_margins(config, params, prompt, served) -> array``
    Correctness of one served request through the family's own plain
    reference (a float32 ``jax.numpy`` file that imports nothing of the
    program): entry j is how far served token j lies below the reference's
    best logit at its position, teacher-forced.

``train_step(config, devices, seed) -> TrainStep``
    What ``generators/train_steps.py`` drives for a ``role: train`` cell:
    ``init(seed) -> state``, the jitted ``step(state, tokens, *tail) ->
    (state, loss)`` as the program built it (so that its jit cache can be
    counted), the ``tail`` of arguments every call repeats, and ``batch``,
    the sequences a step consumes.

``reference_loss(config, seed, tokens) -> float``
    The family's plain reference's loss on ``tokens`` [B, T + 1] with the
    parameters the first step starts from.

``decode_step_cost(sizes, batch_rows, live_kv_tokens) -> dict``
    What one decode step must do: ``bytes`` it has to move and ``flops`` it
    has to do for ``batch_rows`` occupied slots holding ``live_kv_tokens``
    rows of state between them.  ``decode_step_mfu`` holds the step to it.

``train_flops_per_token(sizes, seq_len) -> float``
    Operations the forward and backward passes require per trained token,
    recomputation not counted.  ``train_mfu`` holds the step to it.

The two counts stay with the benchmark, per family, so that 100% is each
architecture's own and no later PR can move it."""
from __future__ import annotations

import collections

TrainStep = collections.namedtuple("TrainStep", "init step tail batch")
