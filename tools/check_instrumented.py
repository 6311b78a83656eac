#!/usr/bin/env python
"""Instrumentation lint: every ``jax.jit(`` site on the decode/serving/
jit hot paths must be routed through ``telemetry.instrument_compile``.

The recompile watch (PR 4) and the device feed (PR 6 — per-step
cost/memory analysis, MFU gauges) both hang off that one choke point: a
new step getter that calls ``jax.jit`` directly compiles in the watch's
blind spot — its retraces are invisible, its FLOPs never captured.
This AST scan makes the blind spot a test failure instead of a code
review hope: a ``jax.jit`` reference (called directly OR passed to
``functools.partial``) counts as instrumented only when it sits inside
the argument list of a call to ``_watch_jit`` (generate.py's wrapper)
or ``instrument_compile`` itself.

Scanned files: ``text/serving.py``, ``text/generate.py``, and every
module under ``jit/`` — the step-function zoo the Engine refactor will
consolidate.  The lint is syntactic by design (no imports, no jax): it
assumes the repo's idiom of ``jax.jit`` attribute access (a
``from jax import jit`` alias would evade it, and also the repo's
review conventions).

Resilience lint (PR 7): the resilience layer's value is that every
degradation is OBSERVABLE, so two more syntactic rules run over the
tree: (a) every call to ``resilience.retry`` must pass its ``name=``
(the telemetry counter identity — ``retry`` counts
``resilience.retries.<name>`` internally, so a nameless call would be
a retry loop invisible to the registry; it is also a TypeError at
runtime, but the lint catches sites a test never executes); (b) every
shed/evict/degrade/recover function on the serving path (name contains
``shed``/``evict``/``oom_degrade``/``recover_wedge``/``fail_request``)
must contain a ``count(...)`` or ``set_runtime_wedge(...)`` call — a
silent degradation path reads as healthy on every dashboard.

Speculative-decoding lint (round 11, same rule family): every spec
accept/propose/fallback path in ``text/serving.py`` (name contains
``spec_accept``/``spec_propose``/``spec_fallback``) must count a
``spec.*`` telemetry counter or delegate to another marker-named
callable — the acceptance rate IS the signal that decides whether
speculation pays for itself (the fallback knob, the router gauge),
so an uncounted accept/reject path silently skews it.

Usage: ``python tools/check_instrumented.py [repo_root]`` — exits 1 and
lists ``file:line`` for every unrouted site.  ``tests/
test_device_telemetry.py`` runs it in tier-1, so a dodge can't merge.
"""
from __future__ import annotations

import ast
import os
import sys

# call names that count as the instrumentation choke point
WRAPPER_NAMES = {"_watch_jit", "instrument_compile"}

# repo-relative files/dirs on the decode/serving/train hot paths
SCAN = (
    os.path.join("paddle_tpu", "text", "serving.py"),
    os.path.join("paddle_tpu", "text", "generate.py"),
    os.path.join("paddle_tpu", "text", "kv_pool.py"),
    os.path.join("paddle_tpu", "text", "adapters.py"),
    os.path.join("paddle_tpu", "jit"),
)

# resilience lint scope: everywhere retry loops / shed sites live
RESIL_SCAN = (
    "paddle_tpu",
    "tools",
)

# a function whose name contains one of these IS a degradation site and
# must record a telemetry counter (directly, or by delegating to another
# marker-named site that does — _evict_to_cap -> _evict_one)
DEGRADE_MARKERS = ("_shed", "shed_", "evict", "oom_degrade",
                   "recover_wedge", "fail_request")
COUNT_NAMES = {"count", "set_runtime_wedge"}

# KV-pool lint (round 8, same rule family): every allocator mutation
# path in text/kv_pool.py — allocation, release, copy-on-write, prefix
# eviction — must count a telemetry counter (directly, or by delegating
# to a marker-named method that does: free_slot -> _decref_free).  A
# silent block leak or an uncounted COW storm reads as healthy on every
# dashboard while the pool quietly starves.
KV_POOL_FILE = os.path.join("paddle_tpu", "text", "kv_pool.py")
KV_MARKERS = ("alloc", "evict", "cow", "free")

# Fleet lint (round 9, same rule family): every router scheduling path
# in text/fleet.py — routing, shedding, wedge drains, prefill handoffs,
# re-routes — must count a ``fleet.*`` telemetry counter (directly, or
# by delegating to another marker-named callable that does).  A fleet
# that silently sheds or re-routes reads as healthy on every dashboard
# while requests quietly vanish.
FLEET_FILE = os.path.join("paddle_tpu", "text", "fleet.py")
FLEET_MARKERS = ("route", "shed", "drain", "handoff")

# TRACE lint (round 20, same rule family): every request-movement path
# in text/fleet.py — prefill handoffs, chain migration, reroute drains,
# request adoption — must PROPAGATE the request's trace context (or
# explicitly drop it: ``req.pop("trace", ...)`` also mentions it).  A
# hop that silently loses the trace_id truncates the fleet waterfall
# mid-request, and the gap is invisible until someone needs the trace.
TRACE_FILE = os.path.join("paddle_tpu", "text", "fleet.py")
TRACE_MARKERS = ("handoff", "migrate", "adopt", "reroute", "drain")

# Speculative-decoding lint (round 11, same rule family): every spec
# accept/propose/fallback path in text/serving.py must count a spec.*
# telemetry counter (directly, or by delegating to another marker-named
# callable) — the acceptance rate drives the fallback knob and the
# router's per-replica gauge, so a silent accept/reject path skews the
# very signal that decides whether speculation pays for itself.
# Round 17 extends the marker family to the tree round: every tree
# propose/accept and constrained branch-prune path must count
# (spec.tree_nodes_proposed / tree_nodes_accepted /
# tree_pruned_constrained) — the accepted-path-length gauge and the
# fallbacks==0 contract for constrained workloads hang off exactly
# these sites.
SPEC_FILE = os.path.join("paddle_tpu", "text", "serving.py")
SPEC_MARKERS = ("spec_accept", "spec_propose", "spec_fallback",
                "tree_propose", "tree_accept", "prune_branch")

# budgeted-admission lint (round 12, same rule family): every
# chunked-prefill co-scheduling path in serving.py — the claim, the
# per-round chunk advance, the graduation — must count a telemetry
# counter (serving.admitting_claims / serving.prefill_chunks_interleaved)
# or delegate to another marker-named path: an invisible admission
# pipeline makes decode-gap regressions undiagnosable
ADMIT_FILE = os.path.join("paddle_tpu", "text", "serving.py")
ADMIT_MARKERS = ("admitting", "advance_admit")

# Admission-control lint (round 13, same rule family): every shed /
# throttle / degrade / rate-limit path across the admission layer
# (text/admission.py and the serving/fleet doors that consult it) must
# count a telemetry counter (admission.* — sheds per class, tenant
# throttles, degradations) or delegate to another marker-named callable.
# Overload policy that shed requests invisibly would read as a healthy
# server with mysteriously missing traffic — the counters ARE the
# operator's evidence that load was refused, not lost.
ADMISSION_FILES = (
    os.path.join("paddle_tpu", "text", "admission.py"),
    os.path.join("paddle_tpu", "text", "serving.py"),
    os.path.join("paddle_tpu", "text", "fleet.py"),
)
ADMISSION_MARKERS = ("_shed", "shed_", "throttle", "degrade",
                     "rate_limit")

# Multi-tenant adapter lint (round 14, same rule family): every adapter
# gather / constraint-mask path across the serving layer and the
# adapters subsystem — the per-slot id gather, the host mask build, the
# per-row constraint application — must count a telemetry counter
# (adapters.* / constraint.*) or delegate to another marker-named
# callable.  Per-adapter traffic and masked-token volume are the
# capacity-planning signals a multi-tenant operator bills/sizes by; a
# silent gather or mask site makes one tenant's load invisible.
ADAPTER_FILES = (
    os.path.join("paddle_tpu", "text", "serving.py"),
    os.path.join("paddle_tpu", "text", "adapters.py"),
)
ADAPTER_MARKERS = ("gather_adapter", "apply_constraint", "mask_logits")

# ENGINE lint (round 15, the step-compilation subsystem): text/engine.py
# is the SINGLE authority for building and caching jitted step
# executables.  Two rules enforce it: (a) any ``jax.jit`` reference OR
# subscript write to a ``*_CACHE``-named object in ``text/*.py`` outside
# ``engine.py`` fails — a stray jit site compiles in the recompile
# watch's blind spot and a stray cache write leaks past Engine.purge;
# (b) inside ``engine.py`` every ``jax.jit`` must sit in a
# ``@register(...)``-decorated builder (whose product Engine.get hands
# to the watch) or in the argument list of the instrumentation wrapper,
# and the ``Engine.get``/``Engine.jit`` choke points themselves must
# call the wrapper — so every registry build routes through
# ``instrument_compile`` by construction.
ENGINE_DIR = os.path.join("paddle_tpu", "text")
ENGINE_FILE = os.path.join("paddle_tpu", "text", "engine.py")

# Prefix-cache lint (round 16, same rule family): every radix-tree /
# spill-tier / affinity path across the prefix cache — the no-copy node
# split, host-RAM demotion, restore-on-adopt, prefix-aware replica
# scoring — must count a telemetry counter (kv_pool.radix_splits /
# kv_pool.spilled_blocks / kv_pool.restored_blocks /
# fleet.prefix_routed) or delegate to another marker-named callable.
# The prefix hit rate is the whole point of the tier; a split or spill
# path that moves KV rows without counting them makes the hit-rate
# gauge a lie.
PREFIX_FILES = (
    os.path.join("paddle_tpu", "text", "kv_pool.py"),
    os.path.join("paddle_tpu", "text", "fleet.py"),
)
PREFIX_MARKERS = ("split", "spill", "restore", "prefix_route")

# STREAM lint (round 18, same rule family): every zero-copy streaming /
# elastic-scaling / chain-migration path across the fleet transport and
# the KV pool — the per-chunk handoff emit, the chunked inject, the
# scale-out/scale-in transitions, cross-replica chain migration — must
# count a telemetry counter (fleet.stream_chunks / fleet.stream_bytes /
# fleet.scale_outs / fleet.scale_ins / kv_pool.chain_migrations) or
# delegate to another marker-named callable.  The chunked handoff's
# whole value claim is measured overlap; an uncounted chunk or silent
# topology change makes the TTFT win and the replica gauge unfalsifiable.
STREAM_FILES = (
    os.path.join("paddle_tpu", "text", "fleet.py"),
    os.path.join("paddle_tpu", "text", "kv_pool.py"),
)
STREAM_MARKERS = ("stream", "scale_out", "scale_in", "migrate")

# STREAM lint rule (b): the raw-row transport exists to get pickle OFF
# the KV handoff path — a deserialization gadget surface AND a full
# host-side copy per hop.  Any ``pickle.`` attribute use (loads, dumps,
# Pickler, ...) or ``import pickle`` in text/fleet.py fails outright.
PICKLE_BAN_FILE = os.path.join("paddle_tpu", "text", "fleet.py")

# MOE lint (round 19, same rule family): every token→expert routing
# path in the MoE serving subsystem — dispatch, combine, capacity-drop
# accounting — must count a telemetry counter (moe.dropped_tokens /
# moe.expert_load) or delegate to another marker-named callable or to
# one of the stats-bearing routing tails (:data:`MOE_DELEGATES`).  The
# capacity-factor trade is the subsystem's whole contract: a routing
# path that drops tokens without counting them turns "bounded drop
# rate" into an unfalsifiable claim and hides expert-load skew.
MOE_FILE = os.path.join("paddle_tpu", "text", "moe_serving.py")
MOE_MARKERS = ("dispatch", "combine", "drop")
MOE_DELEGATES = ("moe_ffn", "_ffn_tail", "_block_post_attn",
                 "drain_drop_stats")


def _call_name(node: ast.Call):
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def scan_source(src: str, filename: str = "<src>") -> list:
    """Violations in one source string: [(filename, lineno, message)].

    A "site" is any ``jax.jit`` attribute access in the AST — covering
    both ``jax.jit(fn, ...)`` calls and ``functools.partial(jax.jit,
    ...)`` decorator forms.  It passes only when an ANCESTOR node is a
    call to one of :data:`WRAPPER_NAMES` (i.e. the freshly built
    executable is handed straight to the instrumentation)."""
    tree = ast.parse(src, filename=filename)
    parents: dict = {}
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            parents[child] = parent
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"):
            continue
        cur, routed = node, False
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, ast.Call) \
                    and _call_name(cur) in WRAPPER_NAMES:
                routed = True
                break
        if not routed:
            violations.append(
                (filename, node.lineno,
                 "jax.jit site not routed through "
                 "telemetry.instrument_compile / generate._watch_jit"))
    return violations


def scan_resilience_source(src: str, filename: str = "<src>") -> list:
    """Resilience-lint violations in one source string.

    Rule (a): a ``retry(...)`` call (bare or attribute — the repo's only
    ``retry`` callables are the resilience primitive and its aliases)
    must carry a ``name=`` keyword.  Rule (b): a function whose name
    marks it a degradation site (:data:`DEGRADE_MARKERS`) must contain a
    call to one of :data:`COUNT_NAMES` somewhere in its body."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node) == "retry":
            if not any(kw.arg == "name" for kw in node.keywords):
                violations.append(
                    (filename, node.lineno,
                     "resilience.retry call without name= (the telemetry "
                     "counter identity — every retry site must be "
                     "observable)"))
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and any(m in node.name for m in DEGRADE_MARKERS):
            counted = any(
                isinstance(n, ast.Call)
                and (_call_name(n) in COUNT_NAMES
                     or any(m in (_call_name(n) or "")
                            for m in DEGRADE_MARKERS))
                for n in ast.walk(node))
            if not counted:
                violations.append(
                    (filename, node.lineno,
                     f"degradation site {node.name}() records no "
                     f"telemetry counter (count/set_runtime_wedge) — "
                     f"silent sheds read as healthy"))
    return violations


def scan_kv_pool_source(src: str, filename: str = "<src>") -> list:
    """KV-pool lint violations in one source string: a function whose
    name carries a :data:`KV_MARKERS` marker must contain a call to one
    of :data:`COUNT_NAMES` or delegate to another marker-named
    callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in KV_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "") for m in KV_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"kv_pool mutation site {node.name}() records no "
                 f"telemetry counter (count) — silent block leaks/COW "
                 f"storms read as healthy"))
    return violations


def scan_fleet_source(src: str, filename: str = "<src>") -> list:
    """Fleet lint violations in one source string: a function whose name
    carries a :data:`FLEET_MARKERS` marker (a router scheduling path)
    must contain a call to one of :data:`COUNT_NAMES` or delegate to
    another marker-named callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in FLEET_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "") for m in FLEET_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"fleet scheduling site {node.name}() records no "
                 f"telemetry counter (count) — silent re-routes/sheds "
                 f"read as healthy while requests vanish"))
    return violations


def _mentions_trace(node) -> bool:
    """Whether any descendant touches trace context: a name/attribute
    containing ``trace`` (``req["trace"]`` reads land here via the
    ``"trace"`` string constant; ``mint_trace``/``_route_spans`` calls
    via the name), or a ``trace=`` keyword on any call."""
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str) \
                and "trace" in n.value:
            return True
        if isinstance(n, ast.Name) and "trace" in n.id:
            return True
        if isinstance(n, ast.Attribute) and "trace" in n.attr:
            return True
        if isinstance(n, ast.keyword) and n.arg and "trace" in n.arg:
            return True
    return False


def scan_trace_source(src: str, filename: str = "<src>") -> list:
    """TRACE lint violations in one source string: a function whose name
    carries a :data:`TRACE_MARKERS` marker (a path that moves a request
    between processes/replicas) must propagate or explicitly drop trace
    context — i.e. mention it per :func:`_mentions_trace` — or delegate
    to another marker-named callable that does."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in TRACE_MARKERS)):
            continue
        passed = _mentions_trace(node) or any(
            isinstance(n, ast.Call)
            and any(m in (_call_name(n) or "") for m in TRACE_MARKERS)
            for n in ast.walk(node))
        if not passed:
            violations.append(
                (filename, node.lineno,
                 f"request-movement site {node.name}() neither "
                 f"propagates nor explicitly drops trace context — the "
                 f"fleet waterfall silently truncates at this hop"))
    return violations


def scan_prefix_cache_source(src: str, filename: str = "<src>") -> list:
    """Prefix-cache lint violations in one source string: a function
    whose name carries a :data:`PREFIX_MARKERS` marker (a radix split,
    spill/restore, or prefix-routing path) must contain a call to one
    of :data:`COUNT_NAMES` or delegate to another marker-named
    callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in PREFIX_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "")
                        for m in PREFIX_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"prefix-cache site {node.name}() records no telemetry "
                 f"counter (count) — uncounted splits/spills make the "
                 f"prefix hit-rate gauge a lie"))
    return violations


def scan_stream_source(src: str, filename: str = "<src>") -> list:
    """STREAM lint violations in one source string: a function whose
    name carries a :data:`STREAM_MARKERS` marker (a chunked-handoff,
    elastic-scaling, or chain-migration path) must contain a call to
    one of :data:`COUNT_NAMES` or delegate to another marker-named
    callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in STREAM_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "")
                        for m in STREAM_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"streaming/elastic path {node.name}() records no "
                 f"telemetry counter (count) — an uncounted chunk or "
                 f"silent scale event makes the overlap win and the "
                 f"replica gauge unfalsifiable"))
    return violations


def scan_pickle_ban_source(src: str, filename: str = "<src>") -> list:
    """STREAM lint rule (b) violations: any ``pickle`` import or
    ``pickle.<attr>`` reference in the fleet transport.  The raw-row
    protocol's security/perf claim is that NO object deserialization
    sits on the KV handoff path — one stray ``pickle.loads`` reopens
    both the gadget surface and the full host-side copy."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == "pickle":
                    violations.append(
                        (filename, node.lineno,
                         "import pickle in the fleet transport — the "
                         "raw-row protocol bans object deserialization "
                         "on the KV handoff path"))
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "pickle":
                violations.append(
                    (filename, node.lineno,
                     "from pickle import ... in the fleet transport — "
                     "the raw-row protocol bans object deserialization "
                     "on the KV handoff path"))
        elif (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "pickle"):
            violations.append(
                (filename, node.lineno,
                 f"pickle.{node.attr} site in the fleet transport — "
                 f"frames are struct-prefixed JSON headers + raw "
                 f"buffers; pickle reopens the gadget surface and the "
                 f"host-side copy"))
    return violations


def scan_moe_source(src: str, filename: str = "<src>") -> list:
    """MOE lint violations in one source string: a function whose name
    carries a :data:`MOE_MARKERS` marker (a token→expert dispatch,
    combine, or capacity-drop path) must contain a call to one of
    :data:`COUNT_NAMES` or delegate to another marker-named callable or
    to a stats-bearing routing tail in :data:`MOE_DELEGATES`."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in MOE_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "")
                        for m in MOE_MARKERS + MOE_DELEGATES))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"MoE routing path {node.name}() records no telemetry "
                 f"counter (count) — uncounted dispatch/combine/drop "
                 f"makes the capacity-factor drop rate and expert-load "
                 f"balance unfalsifiable"))
    return violations


def scan_spec_source(src: str, filename: str = "<src>") -> list:
    """Speculative-decoding lint violations in one source string: a
    function whose name carries a :data:`SPEC_MARKERS` marker (a spec
    accept/propose/fallback path) must contain a call to one of
    :data:`COUNT_NAMES` or delegate to another marker-named callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in SPEC_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "") for m in SPEC_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"speculative path {node.name}() records no telemetry "
                 f"counter (count) — an uncounted accept/reject/fallback "
                 f"skews the acceptance rate that gates speculation"))
    return violations


def scan_admit_source(src: str, filename: str = "<src>") -> list:
    """Budgeted-admission lint violations in one source string: a
    function whose name carries an :data:`ADMIT_MARKERS` marker (a
    chunked-prefill claim/advance/graduate path) must contain a call to
    one of :data:`COUNT_NAMES` or delegate to another marker-named
    callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in ADMIT_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "") for m in ADMIT_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"budgeted-admission path {node.name}() records no "
                 f"telemetry counter (count) — an uncounted "
                 f"claim/chunk-advance makes admission stalls and "
                 f"decode-gap regressions undiagnosable"))
    return violations


def scan_admission_source(src: str, filename: str = "<src>") -> list:
    """Admission-control lint violations in one source string: a
    function whose name carries an :data:`ADMISSION_MARKERS` marker (a
    shed/throttle/degrade/rate-limit path) must contain a call to one
    of :data:`COUNT_NAMES` or delegate to another marker-named
    callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in ADMISSION_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "")
                        for m in ADMISSION_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"admission-control path {node.name}() records no "
                 f"telemetry counter (count) — an uncounted shed/"
                 f"throttle reads as a healthy server with missing "
                 f"traffic"))
    return violations


def scan_adapter_source(src: str, filename: str = "<src>") -> list:
    """Multi-tenant adapter lint violations in one source string: a
    function whose name carries an :data:`ADAPTER_MARKERS` marker (an
    adapter-gather or constraint-mask path) must contain a call to one
    of :data:`COUNT_NAMES` or delegate to another marker-named
    callable."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and any(m in node.name for m in ADAPTER_MARKERS)):
            continue
        counted = any(
            isinstance(n, ast.Call)
            and (_call_name(n) in COUNT_NAMES
                 or any(m in (_call_name(n) or "")
                        for m in ADAPTER_MARKERS))
            for n in ast.walk(node))
        if not counted:
            violations.append(
                (filename, node.lineno,
                 f"multi-tenant adapter path {node.name}() records no "
                 f"telemetry counter (count) — an uncounted gather/mask "
                 f"makes one tenant's load invisible to capacity "
                 f"planning"))
    return violations


def scan_engine_outside_source(src: str, filename: str = "<src>") -> list:
    """ENGINE lint rule (a), for a ``text/*.py`` module that is NOT
    engine.py: any ``jax.jit`` attribute reference fails (compilation
    belongs to the Engine's registry/``jit`` choke points), and any
    subscript WRITE to a ``*_CACHE``-named object fails (the Engine owns
    its executable caches; a side-door write is an entry ``purge`` can
    never see retired)."""
    tree = ast.parse(src, filename=filename)
    violations = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"):
            violations.append(
                (filename, node.lineno,
                 "jax.jit outside text/engine.py — route the build "
                 "through engine.ENGINE.get (a registry kind) or "
                 "engine.ENGINE.jit (the generic choke point)"))
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for tgt in targets:
            if (isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id.endswith("_CACHE")):
                violations.append(
                    (filename, tgt.lineno,
                     f"step-cache write {tgt.value.id}[...] outside "
                     f"text/engine.py — the Engine owns its caches "
                     f"(Engine.get stores; Engine.purge retires)"))
    return violations


def scan_engine_file_source(src: str, filename: str = "<src>") -> list:
    """ENGINE lint rule (b), for engine.py itself: every ``jax.jit``
    must sit inside a ``@register(...)``-decorated builder (Engine.get
    instruments its product) or in the argument list of the
    instrumentation wrapper, and the ``Engine.get``/``Engine.jit``
    choke points must themselves call the wrapper — together these
    guarantee every registry build routes through
    ``instrument_compile``."""
    tree = ast.parse(src, filename=filename)
    parents: dict = {}
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            parents[child] = parent
    registered = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if (isinstance(dec, ast.Call)
                        and _call_name(dec) == "register") \
                        or (isinstance(dec, ast.Name)
                            and dec.id == "register"):
                    registered.add(node)
    violations = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"):
            continue
        cur, routed = node, False
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, ast.Call) \
                    and _call_name(cur) in WRAPPER_NAMES:
                routed = True
                break
            if cur in registered:
                routed = True
                break
        if not routed:
            violations.append(
                (filename, node.lineno,
                 "jax.jit in engine.py outside a @register(...) builder "
                 "or the instrumentation wrapper — Engine.get can never "
                 "hand this executable to the recompile watch"))
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef) and node.name == "Engine"):
            continue
        for fn in node.body:
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and fn.name in ("get", "jit"):
                routed = any(
                    isinstance(n, ast.Call)
                    and _call_name(n) in WRAPPER_NAMES
                    for n in ast.walk(fn))
                if not routed:
                    violations.append(
                        (filename, fn.lineno,
                         f"Engine.{fn.name}() never calls "
                         f"instrument_compile/_watch_jit — every build "
                         f"through this choke point compiles in the "
                         f"recompile watch's blind spot"))
    return violations


def _walk_py(path: str) -> list:
    out = []
    for dirpath, _, names in sorted(os.walk(path)):
        out.extend(os.path.join(dirpath, f) for f in sorted(names)
                   if f.endswith(".py"))
    return out


def scan_repo(root: str | None = None) -> list:
    """Violations across every scanned hot-path module."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = []
    for rel in SCAN:
        path = os.path.join(root, rel)
        if os.path.isdir(path):
            # recursive: a future jit/ subpackage (the Engine refactor)
            # must not evade the lint by nesting its modules
            files.extend(_walk_py(path))
        elif os.path.exists(path):
            files.append(path)
    violations = []
    for path in files:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        violations.extend(scan_source(src, os.path.relpath(path, root)))
    # resilience lint: retry/shed observability across the wider tree
    resil_files = []
    for rel in RESIL_SCAN:
        path = os.path.join(root, rel)
        if os.path.isdir(path):
            resil_files.extend(_walk_py(path))
        elif os.path.exists(path):
            resil_files.append(path)
    for path in resil_files:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        violations.extend(
            scan_resilience_source(src, os.path.relpath(path, root)))
    # kv-pool lint: allocator mutation observability
    kv_path = os.path.join(root, KV_POOL_FILE)
    if os.path.exists(kv_path):
        with open(kv_path, encoding="utf-8") as f:
            violations.extend(scan_kv_pool_source(
                f.read(), os.path.relpath(kv_path, root)))
    # fleet lint: router scheduling observability
    fleet_path = os.path.join(root, FLEET_FILE)
    if os.path.exists(fleet_path):
        with open(fleet_path, encoding="utf-8") as f:
            violations.extend(scan_fleet_source(
                f.read(), os.path.relpath(fleet_path, root)))
    # TRACE lint: trace-context propagation through request movement
    trace_path = os.path.join(root, TRACE_FILE)
    if os.path.exists(trace_path):
        with open(trace_path, encoding="utf-8") as f:
            violations.extend(scan_trace_source(
                f.read(), os.path.relpath(trace_path, root)))
    # prefix-cache lint: radix split / spill / restore / affinity
    # observability
    for rel in PREFIX_FILES:
        px_path = os.path.join(root, rel)
        if os.path.exists(px_path):
            with open(px_path, encoding="utf-8") as f:
                violations.extend(scan_prefix_cache_source(
                    f.read(), os.path.relpath(px_path, root)))
    # STREAM lint: chunked handoff / elastic scaling / chain migration
    # observability, plus the pickle ban on the fleet transport
    for rel in STREAM_FILES:
        st_path = os.path.join(root, rel)
        if os.path.exists(st_path):
            with open(st_path, encoding="utf-8") as f:
                violations.extend(scan_stream_source(
                    f.read(), os.path.relpath(st_path, root)))
    pb_path = os.path.join(root, PICKLE_BAN_FILE)
    if os.path.exists(pb_path):
        with open(pb_path, encoding="utf-8") as f:
            violations.extend(scan_pickle_ban_source(
                f.read(), os.path.relpath(pb_path, root)))
    # MOE lint: token→expert dispatch/combine/drop observability
    moe_path = os.path.join(root, MOE_FILE)
    if os.path.exists(moe_path):
        with open(moe_path, encoding="utf-8") as f:
            violations.extend(scan_moe_source(
                f.read(), os.path.relpath(moe_path, root)))
    # speculative-decoding lint: accept/propose/fallback observability
    spec_path = os.path.join(root, SPEC_FILE)
    if os.path.exists(spec_path):
        with open(spec_path, encoding="utf-8") as f:
            violations.extend(scan_spec_source(
                f.read(), os.path.relpath(spec_path, root)))
    # budgeted-admission lint: chunked-prefill co-scheduling observability
    admit_path = os.path.join(root, ADMIT_FILE)
    if os.path.exists(admit_path):
        with open(admit_path, encoding="utf-8") as f:
            violations.extend(scan_admit_source(
                f.read(), os.path.relpath(admit_path, root)))
    # admission-control lint: shed/throttle/degrade observability
    for rel in ADMISSION_FILES:
        adm_path = os.path.join(root, rel)
        if os.path.exists(adm_path):
            with open(adm_path, encoding="utf-8") as f:
                violations.extend(scan_admission_source(
                    f.read(), os.path.relpath(adm_path, root)))
    # multi-tenant adapter lint: gather/constraint-mask observability
    for rel in ADAPTER_FILES:
        ad_path = os.path.join(root, rel)
        if os.path.exists(ad_path):
            with open(ad_path, encoding="utf-8") as f:
                violations.extend(scan_adapter_source(
                    f.read(), os.path.relpath(ad_path, root)))
    # ENGINE lint: the Engine is the single compilation/caching authority
    eng_dir = os.path.join(root, ENGINE_DIR)
    eng_file = os.path.join(root, ENGINE_FILE)
    if os.path.isdir(eng_dir):
        for path in _walk_py(eng_dir):
            if os.path.abspath(path) == os.path.abspath(eng_file):
                continue
            with open(path, encoding="utf-8") as f:
                violations.extend(scan_engine_outside_source(
                    f.read(), os.path.relpath(path, root)))
    if os.path.exists(eng_file):
        with open(eng_file, encoding="utf-8") as f:
            violations.extend(scan_engine_file_source(
                f.read(), os.path.relpath(eng_file, root)))
    return violations


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else None
    violations = scan_repo(root)
    if not violations:
        print("check_instrumented: every jax.jit site is routed through "
              "the recompile watch")
        return 0
    for fname, line, msg in violations:
        print(f"{fname}:{line}: {msg}", file=sys.stderr)
    print(f"check_instrumented: {len(violations)} unrouted jax.jit "
          f"site(s) — new step getters must funnel through "
          f"telemetry.instrument_compile", file=sys.stderr)
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
