"""Pipeline parallelism — tick-table microbatch schedules over a mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2C); this module
fills the reserved ``stage`` mesh axis with a family of TPU-idiomatic
schedules over ONE stacked-params representation: every device holds one
(or ``n_virtual``) stage's parameters (stacked pytree sharded over
``stage``), activations hop stage→stage over ICI via ``lax.ppermute``,
and each schedule is a single ``lax.scan`` over a **precomputed static
tick table** inside ``shard_map`` — one compiled program, no host-side
stage loop.  All schedules compute exactly the serial fold of the
stages (same math, different WHERE/WHEN — the trajectory-equality
discipline pins this).

Schedules (``pipeline_apply(..., schedule=)``; taxonomy per arXiv
2412.14374):

``gpipe``
    The original scan: at tick t stage s computes microbatch t-s, the
    backward is jax autodiff of the scan (reversed replay).  Bubble
    fraction (S-1)/(S+M-1) per pass; autodiff stores O(S+M-1) ticks of
    scan state per device unless ``remat=True``.
``1f1b``
    One-forward-one-backward over the tick-table engine: the backward
    pass is hand-scheduled (``jax.custom_vjp``), draining cotangents as
    soon as they arrive instead of replaying the forward scan in
    reverse.  With ``remat=True`` the backward interleaves forward
    recomputes with backwards, keeping the in-flight activation stash
    bounded at ~S microbatches (host-verified slot allocation) — the
    memory win over GPipe.  With ``remat=False`` the value pass stashes
    only the per-stage *boundary* activations ([V, M] microbatch inputs
    per device) and the backward is a lean reverse pipeline — still far
    below GPipe-autodiff's full per-tick residuals.
``interleaved``
    1F1B with ``n_virtual`` virtual stages per device (stacked params
    carry V stages per device, assigned round-robin so hops stride the
    stage ring); the ramp shrinks by ~V, cutting the bubble toward
    (S-1)/(V·(S+M-1)).
``zb``
    Zero-bubble-style split backward (experimental): the backward of
    each stage is split into an input-grad half (critical path) and a
    weight-grad half (fills former bubble slots), per the zero-bubble
    schedule family.  Same math — the two vjp halves sum to the full
    vjp.

Every hop and broadcast self-accounts analytic bytes at trace time
through ``parallel/comm_stats.py``, attributed per schedule and hop
kind (``comm_bytes_by_hop{schedule=,hop=}``), and each built schedule
records its analytic bubble fraction (idle tick-table slots) into
``pipeline_schedule_info()`` and the
``train_pipeline_bubble_fraction{schedule=}`` gauge.
"""

from __future__ import annotations

import functools
import heapq
import threading
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ml_trainer_tpu.parallel.comm_stats import (
    _tree_bytes,
    account as _account,
    record_collective as _record_collective,
    record_hop as _record_hop,
)

SCHEDULES = ("gpipe", "1f1b", "interleaved", "zb")
PIPELINE_SCHEDULES = SCHEDULES  # public alias (parallel/__init__.py)

# Tick-table action codes.  ``zb`` splits the backward: B_X produces the
# input cotangent (critical path), B_W the weight gradient (bubble
# filler); other schedules use the fused B.
_IDLE, _F, _B, _BW = 0, 1, 2, 3

_info_lock = threading.Lock()
_SCHEDULE_INFO: Dict[str, dict] = {}


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack a list of per-stage parameter pytrees (identical structure)
    into one pytree with a leading stage dim — the layout that shards over
    the ``stage`` mesh axis with ``P('stage', ...)``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_schedule_info() -> Dict[str, dict]:
    """Per-schedule build info recorded at trace time: tick counts,
    analytic bubble (idle tick-table slot) fractions, and stash sizing.
    Keyed by schedule name; the latest build per schedule wins."""
    with _info_lock:
        return {k: dict(v) for k, v in _SCHEDULE_INFO.items()}


def reset_pipeline_info() -> None:
    with _info_lock:
        _SCHEDULE_INFO.clear()


def _record_info(schedule: str, info: dict) -> None:
    with _info_lock:
        _SCHEDULE_INFO[schedule] = dict(info)
    try:
        from ml_trainer_tpu.telemetry.registry import default_registry

        default_registry().gauge(
            "train_pipeline_bubble_fraction",
            "analytic pipeline bubble: fraction of device-tick slots "
            "idle in the schedule's tick tables (forward + backward)",
            ("schedule",),
        ).labels(schedule=schedule).set(float(info["bubble_fraction"]))
    except Exception:  # registry trouble must never break a trace
        pass


# --------------------------------------------------------------- scheduler
class _Tables:
    """Static tick tables for one pass of one schedule (host numpy)."""

    def __init__(self, n_ticks, n_dev, n_f_slots, n_b_slots):
        shape = (max(n_ticks, 1), n_dev)
        z = lambda: np.zeros(shape, np.int32)
        self.kind = z()
        self.mb = z()
        self.vs = z()
        self.first = z()
        self.last = z()
        self.ycap = z()
        self.dxcap = z()
        self.arg_f = z()
        self.arg_b = z()
        # Default recv slot = trash row (index n_slots): payloads nobody
        # scheduled (idle-tick zeros, the last stage's unconsumed output)
        # land there and are never read.
        self.recv_f = np.full(shape, n_f_slots, np.int32)
        self.recv_b = np.full(shape, n_b_slots, np.int32)
        self.n_ticks = n_ticks
        self.n_f_slots = n_f_slots
        self.n_b_slots = n_b_slots
        self.n_actions = 0
        # Per-kind action counts for the executed-compute waste model.
        self.n_f = 0
        self.n_b = 0
        self.n_w = 0

    def as_jnp(self) -> dict:
        return {
            k: jnp.asarray(getattr(self, k))
            for k in ("kind", "mb", "vs", "first", "last", "ycap",
                      "dxcap", "arg_f", "arg_b", "recv_f", "recv_b")
        }

    @property
    def idle_fraction(self) -> float:
        total = self.n_ticks * self.kind.shape[1]
        return 1.0 - self.n_actions / total if total else 0.0


def _alloc_slots(payloads: dict):
    """Assign buffer slots to payloads: ``payloads`` maps key ->
    (arrival_tick, device, last_use_tick).  A slot consumed at tick t is
    reusable for arrivals at t+1 (the scan body stores the arriving hop
    BEFORE computing, so a same-tick reuse would clobber the value being
    read).  Returns (recv{(tick, dev): slot}, slot_of{key: slot},
    n_slots)."""
    by_dev: Dict[int, list] = {}
    for key, (arrive, dev, last_use) in payloads.items():
        by_dev.setdefault(dev, []).append((arrive, last_use, key))
    recv, slot_of, n_slots = {}, {}, 0
    for dev, plist in by_dev.items():
        plist.sort()
        active: list = []  # (last_use, slot) min-heap
        free: list = []
        hi = 0
        for arrive, last_use, key in plist:
            while active and active[0][0] < arrive:
                heapq.heappush(free, heapq.heappop(active)[1])
            slot = heapq.heappop(free) if free else hi
            if not free and slot == hi:
                hi += 1
            heapq.heappush(active, (last_use, slot))
            recv[(arrive, dev)] = slot
            slot_of[key] = slot
        n_slots = max(n_slots, hi)
    return recv, slot_of, n_slots


@functools.lru_cache(maxsize=64)
def _build_tables(schedule: str, n_dev: int, n_virtual: int, n_micro: int,
                  mode: str) -> _Tables:
    """Greedy list-schedule one pass of ``schedule`` into static tick
    tables.  ``mode``:

    * ``'fwd'`` — the value pass: forwards only.
    * ``'bwd_stash'`` — backward over stashed boundary activations
      (``remat=False``): backwards only, a lean reverse pipeline.
    * ``'bwd_recompute'`` — combined pass (``remat=True``): forward
      recomputes interleaved with backwards, in-flight stash bounded at
      ~S microbatches by construction (1F1B's memory contract).

    Dependencies model the scan's communication exactly: an action's
    output hops at the START of the next tick, so a consumer on the
    neighbouring device is ready at ``producer_tick + 1`` (and may fire
    that very tick — the body stores arrivals before computing).
    """
    S, V, M = int(n_dev), int(n_virtual), int(n_micro)
    G = S * V
    zb = schedule == "zb" and mode != "fwd"

    if mode == "fwd":
        f_need = {(g, i) for g in range(G) for i in range(M)}
    elif mode == "bwd_recompute":
        # The last global stage's recompute is folded into its B's vjp
        # (jax.vjp re-runs the forward to linearize) — scheduling it
        # separately would be pure waste.
        f_need = {(g, i) for g in range(G - 1) for i in range(M)}
    else:
        f_need = set()
    b_need = (set() if mode == "fwd"
              else {(g, i) for g in range(G) for i in range(M)})
    w_need = set(b_need) if zb else set()

    done_f: dict = {}
    done_b: dict = {}
    done_w: dict = {}
    b_count = [0] * G  # completed B (B_X) per stage — the 1F1B cap releaser
    acts: Dict[int, Dict[int, tuple]] = {}
    t, limit = 0, 16 * (G + M + 4) * (V + 2)
    while f_need or b_need or w_need:
        if t > limit:
            raise RuntimeError(
                f"pipeline scheduler stuck: {schedule} S={S} V={V} M={M} "
                f"mode={mode}"
            )
        for d in range(S):
            best = None
            # B (or B_X) first: drain cotangents as soon as they arrive —
            # the 1F1B discipline (and what bounds the stash).
            for (g, i) in b_need:
                if g % S != d:
                    continue
                if (mode == "bwd_recompute" and g > 0
                        and done_f.get((g - 1, i), t) + 1 > t):
                    continue  # stage input not recomputed/arrived yet
                if g < G - 1 and done_b.get((g + 1, i), t) + 1 > t:
                    continue  # cotangent not arrived yet
                key = (i, -g)
                if best is None or key < best[0]:
                    best = (key, "B", g, i)
            if best is None:
                for (g, i) in f_need:
                    if g % S != d:
                        continue
                    if g > 0 and done_f.get((g - 1, i), t) + 1 > t:
                        continue
                    if i > 0 and (g, i - 1) not in done_f:
                        continue  # per-stage microbatch order
                    # 1F1B warmup cap: stage g keeps at most G-g
                    # microbatches in flight, so the stash stays O(S·V).
                    if b_need and i - b_count[g] >= G - g:
                        continue
                    key = (i, g)
                    if best is None or key < best[0]:
                        best = (key, "F", g, i)
            if best is None:
                # Weight-grad halves (zb) fill whatever slots remain.
                for (g, i) in w_need:
                    if g % S != d:
                        continue
                    if done_b.get((g, i), t) + 1 > t:
                        continue
                    key = (i, -g)
                    if best is None or key < best[0]:
                        best = (key, "W", g, i)
            if best is None:
                continue
            _, what, g, i = best
            acts.setdefault(t, {})[d] = (what, g, i)
            if what == "F":
                done_f[(g, i)] = t
                f_need.discard((g, i))
            elif what == "B":
                done_b[(g, i)] = t
                b_need.discard((g, i))
                b_count[g] += 1
            else:
                done_w[(g, i)] = t
                w_need.discard((g, i))
        t += 1

    n_ticks = (max(acts) + 1) if acts else 0

    # Payload lifetimes -> buffer slots.  Forward payload (g -> g+1, i):
    # produced by F(g, i), consumed by F(g+1, i) and/or the backward of
    # stage g+1 (both halves under zb).
    f_pay: dict = {}
    for (g, i), tf in done_f.items():
        if g + 1 > G - 1:
            continue  # the last stage's output is y, captured not hopped
        uses = [done_x[(g + 1, i)]
                for done_x in (done_f, done_b, done_w)
                if (g + 1, i) in done_x]
        if uses:
            f_pay[(g, i)] = (tf + 1, (g + 1) % S, max(uses))
    b_pay: dict = {}
    for (g, i), tb in done_b.items():
        if g == 0:
            continue  # dx, captured not hopped
        uses = [done_x[(g - 1, i)]
                for done_x in (done_b, done_w)
                if (g - 1, i) in done_x]
        if uses:
            b_pay[(g, i)] = (tb + 1, (g - 1) % S, max(uses))
    recv_f, slot_f, nf = _alloc_slots(f_pay)
    recv_b, slot_b, nb = _alloc_slots(b_pay)

    tabs = _Tables(n_ticks, S, nf, nb)
    for (arrive, dev), slot in recv_f.items():
        if arrive < n_ticks:
            tabs.recv_f[arrive, dev] = slot
    for (arrive, dev), slot in recv_b.items():
        if arrive < n_ticks:
            tabs.recv_b[arrive, dev] = slot
    for t, per_dev in acts.items():
        for d, (what, g, i) in per_dev.items():
            tabs.n_actions += 1
            if what == "F":
                tabs.kind[t, d] = _F
                tabs.n_f += 1
            elif what == "B":
                tabs.kind[t, d] = _B
                tabs.n_b += 1
            else:
                tabs.kind[t, d] = _BW
                tabs.n_w += 1
            tabs.mb[t, d] = i
            tabs.vs[t, d] = g // S
            tabs.first[t, d] = int(g == 0)
            tabs.last[t, d] = int(g == G - 1)
            if mode == "fwd":
                tabs.ycap[t, d] = int(what == "F" and g == G - 1)
            if what == "B" and g == 0:
                tabs.dxcap[t, d] = 1
            if g > 0 and what in ("F", "B", "W") and (g - 1, i) in slot_f:
                tabs.arg_f[t, d] = slot_f[(g - 1, i)]
            if what in ("B", "W") and g < G - 1 and (g + 1, i) in slot_b:
                tabs.arg_b[t, d] = slot_b[(g + 1, i)]
    return tabs


# ------------------------------------------------------------- primitives
def _ring_broadcast(val, root: int, axis_name: str, *, schedule: str,
                    hop: str):
    """Broadcast ``val`` from ``root`` to every device on the axis by
    recursive doubling over partial ``ppermute`` perms: ceil(log2 S)
    calls, (S-1)·size total wire bytes — half the ring all-reduce the
    old output ``psum`` paid (and no reduction compute).  Each call's
    analytic bytes are recorded per participant (size · active pairs /
    S) against the schedule's hop ledger."""
    n = lax.axis_size(axis_name)
    if n <= 1:
        return val
    stage = lax.axis_index(axis_name)
    dist = (stage - root) % n
    size = _tree_bytes(val)
    k = 1
    while k < n:
        pairs = [((root + i) % n, (root + i + k) % n)
                 for i in range(k) if i + k < n]
        recv = lax.ppermute(val, axis_name, pairs)
        val = jnp.where((dist >= k) & (dist < 2 * k), recv, val)
        try:
            b = float(size) * len(pairs) / n
            _record_collective("ppermute", b, calls=1)
            _record_hop(schedule, hop, b, calls=1)
        except Exception:
            pass
        k *= 2
    return val


# ------------------------------------------------------------ gpipe (scan)
def _pipeline_local(params, x, *, stage_fn, axis_name, n_micro, remat):
    """Per-device GPipe body under shard_map (the original schedule).

    params: this device's stage params (leading stage dim of size 1).
    x: the full [n_micro, mb, ...] microbatched input (replicated).
    """
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: p[0], params)  # drop the stage dim
    mb_shape = x.shape[1:]
    fwd_perm = [(s, s + 1) for s in range(n_stages - 1)]
    if remat:
        # Differentiating through the scan stores every tick's stage
        # activations for the backward — O(S + M - 1) ticks of them per
        # device.  Checkpointing the stage body keeps only the scan carry
        # and recomputes the body during the reverse pass: activation
        # memory drops to O(1) ticks for one extra forward of compute,
        # the standard pipeline-training trade.
        stage_fn = jax.checkpoint(stage_fn)

    def tick(carry, t):
        prev_out, outputs = carry
        # Activations computed last tick hop to the next stage.
        recv = lax.ppermute(prev_out, axis_name, fwd_perm)
        # Stage 0 injects microbatch t (zeros past the ramp); others consume
        # the hop.  Indexing is clamped — masked ticks compute garbage that
        # is never written anywhere.
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        my_in = jnp.where(
            stage == 0,
            lax.dynamic_index_in_dim(x, mb_idx, keepdims=False),
            recv,
        )
        out = stage_fn(params, my_in)
        # The last stage finishes microbatch t-(S-1) at tick t.
        done_idx = t - (n_stages - 1)
        is_done = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
        outputs = lax.cond(
            is_done,
            lambda o: lax.dynamic_update_index_in_dim(
                o, out, jnp.clip(done_idx, 0, n_micro - 1), 0
            ),
            lambda o: o,
            outputs,
        )
        return (out, outputs), None

    init = (
        jnp.zeros(mb_shape, x.dtype),
        jnp.zeros((n_micro,) + mb_shape, x.dtype),
    )
    # The hop inside tick() traces once but runs every scan iteration:
    # account it here with the static tick count instead.
    _account("ppermute", init[0], axis_name,
             times=n_micro + n_stages - 1, hop=("gpipe", "fwd"))
    (_, outputs), _ = lax.scan(
        tick, init, jnp.arange(n_micro + n_stages - 1)
    )
    # Only the last stage holds real outputs.  The old implementation
    # psum-broadcast the full [n_micro, mb, ...] tensor from EVERY stage
    # (all but one contributing zeros — 2·(S-1)/S·size per participant);
    # a last-stage ring broadcast moves half the bytes and adds nothing.
    return _ring_broadcast(outputs, n_stages - 1, axis_name,
                           schedule="gpipe", hop="output_broadcast")


# ----------------------------------------------------- tick-table engine
def _row_at(tables: dict, stage):
    """This device's scalar entries of one tick's table row."""
    return {k: v[stage] for k, v in tables.items()}


def _engine_fwd_local(params, x, *, stage_fn, axis_name, tables, n_f_slots,
                      n_ticks, n_virtual, want_stash, schedule):
    """Value pass: forwards only, idle slots genuinely skipped
    (``lax.switch``), finished microbatches captured on the last stage
    and ring-broadcast at the end.  With ``want_stash`` every stage
    input is also written into a [V, M] boundary-activation stash — the
    ``remat=False`` backward's residuals."""
    S = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_micro, mb_shape = x.shape[0], x.shape[1:]
    fwd_perm = [(s, (s + 1) % S) for s in range(S)]
    zero_mb = jnp.zeros(mb_shape, x.dtype)
    _account("ppermute", zero_mb, axis_name, times=n_ticks,
             hop=(schedule, "fwd"))

    carry = {
        "msg": zero_mb,
        "buf": jnp.zeros((n_f_slots + 1,) + mb_shape, x.dtype),
        "y": jnp.zeros((n_micro,) + mb_shape, x.dtype),
    }
    if want_stash:
        carry["stash"] = jnp.zeros((n_virtual, n_micro) + mb_shape, x.dtype)

    def tick(carry, row):
        r = _row_at(row, stage)
        recv = lax.ppermute(carry["msg"], axis_name, fwd_perm)
        buf = lax.dynamic_update_index_in_dim(
            carry["buf"], recv, r["recv_f"], 0
        )
        a_in = jnp.where(
            r["first"] > 0,
            lax.dynamic_index_in_dim(x, r["mb"], keepdims=False),
            lax.dynamic_index_in_dim(buf, r["arg_f"], keepdims=False),
        )
        pv = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, r["vs"], keepdims=False),
            params,
        )
        out = lax.switch(r["kind"], (
            lambda op: jnp.zeros(mb_shape, x.dtype),
            lambda op: stage_fn(op[0], op[1]).astype(x.dtype),
        ), (pv, a_in))
        y = lax.cond(
            r["ycap"] > 0,
            lambda yy: lax.dynamic_update_index_in_dim(yy, out, r["mb"], 0),
            lambda yy: yy,
            carry["y"],
        )
        new = {"msg": out, "buf": buf, "y": y}
        if "stash" in carry:
            new["stash"] = lax.cond(
                r["kind"] > 0,
                lambda ss: lax.dynamic_update_slice(
                    ss, a_in[None, None],
                    (r["vs"], r["mb"]) + (0,) * len(mb_shape),
                ),
                lambda ss: ss,
                carry["stash"],
            )
        return new, None

    carry, _ = lax.scan(tick, carry, tables)
    y = _ring_broadcast(carry["y"], S - 1, axis_name,
                        schedule=schedule, hop="output_broadcast")
    return (y, carry["stash"]) if want_stash else (y,)


def _engine_bwd_local(params, x, stash, dy, *, stage_fn, axis_name, tables,
                      n_f_slots, n_b_slots, n_ticks, recompute, schedule,
                      batch_axis=None):
    """Backward pass: the hand-scheduled scan over the combined
    (``recompute=True``) or backward-only (stash) tick table.  Each tick
    at most one action per device via ``lax.switch``: forward recompute,
    fused backward (``jax.vjp`` of the stage), or the zb split halves.
    Param grads accumulate per local virtual stage; the input cotangent
    is captured on device 0 and ring-broadcast out."""
    S = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_micro, mb_shape = x.shape[0], x.shape[1:]
    fwd_perm = [(s, (s + 1) % S) for s in range(S)]
    bwd_perm = [(s, (s - 1) % S) for s in range(S)]
    zero_mb = jnp.zeros(mb_shape, x.dtype)
    zero_dp = jax.tree.map(lambda p: jnp.zeros(p.shape[1:], p.dtype), params)
    _account("ppermute", zero_mb, axis_name, times=n_ticks,
             hop=(schedule, "bwd"))
    if recompute:
        _account("ppermute", zero_mb, axis_name, times=n_ticks,
                 hop=(schedule, "fwd_recompute"))

    carry = {
        "mb_": zero_mb,  # backward-direction message (cotangent hop)
        "bbuf": jnp.zeros((n_b_slots + 1,) + mb_shape, x.dtype),
        "grads": jax.tree.map(jnp.zeros_like, params),
        "dx": jnp.zeros_like(x),
    }
    if recompute:
        carry["mf"] = zero_mb
        carry["fbuf"] = jnp.zeros((n_f_slots + 1,) + mb_shape, x.dtype)

    def tick(carry, row):
        r = _row_at(row, stage)
        recv_b = lax.ppermute(carry["mb_"], axis_name, bwd_perm)
        bbuf = lax.dynamic_update_index_in_dim(
            carry["bbuf"], recv_b, r["recv_b"], 0
        )
        if recompute:
            recv_f = lax.ppermute(carry["mf"], axis_name, fwd_perm)
            fbuf = lax.dynamic_update_index_in_dim(
                carry["fbuf"], recv_f, r["recv_f"], 0
            )
            a_in = jnp.where(
                r["first"] > 0,
                lax.dynamic_index_in_dim(x, r["mb"], keepdims=False),
                lax.dynamic_index_in_dim(fbuf, r["arg_f"], keepdims=False),
            )
        else:
            fbuf = None
            # Boundary activations were stashed in the value pass —
            # including stage 0's (== x[mb]), so no injection mux.
            a_in = lax.dynamic_slice(
                stash, (r["vs"], r["mb"]) + (0,) * len(mb_shape),
                (1, 1) + mb_shape,
            ).reshape(mb_shape)
        g_in = jnp.where(
            r["last"] > 0,
            lax.dynamic_index_in_dim(dy, r["mb"], keepdims=False),
            lax.dynamic_index_in_dim(bbuf, r["arg_b"], keepdims=False),
        )
        pv = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, r["vs"], keepdims=False),
            params,
        )

        def br_idle(op):
            return zero_mb, zero_mb, zero_dp

        def br_fwd(op):
            pvv, a, g = op
            return stage_fn(pvv, a).astype(x.dtype), zero_mb, zero_dp

        def br_bwd(op):
            pvv, a, g = op
            out, pull = jax.vjp(stage_fn, pvv, a)
            dp, da = pull(g.astype(out.dtype))
            return zero_mb, da.astype(x.dtype), dp

        def br_bwd_x(op):
            pvv, a, g = op
            out, pull = jax.vjp(lambda aa: stage_fn(pvv, aa), a)
            (da,) = pull(g.astype(out.dtype))
            return zero_mb, da.astype(x.dtype), zero_dp

        def br_bwd_w(op):
            pvv, a, g = op
            out, pull = jax.vjp(lambda pp: stage_fn(pp, a), pvv)
            (dp,) = pull(g.astype(out.dtype))
            return zero_mb, zero_mb, dp

        branches = (
            (br_idle, br_fwd, br_bwd_x, br_bwd_w)
            if schedule == "zb" else (br_idle, br_fwd, br_bwd)
        )
        out_f, out_b, dp = lax.switch(r["kind"], branches, (pv, a_in, g_in))
        grads = jax.tree.map(
            lambda acc, d: acc.at[r["vs"]].add(d), carry["grads"], dp
        )
        dx = lax.cond(
            r["dxcap"] > 0,
            lambda dd: lax.dynamic_update_index_in_dim(dd, out_b, r["mb"], 0),
            lambda dd: dd,
            carry["dx"],
        )
        new = {"mb_": out_b, "bbuf": bbuf, "grads": grads, "dx": dx}
        if recompute:
            new["mf"] = out_f
            new["fbuf"] = fbuf
        return new, None

    carry, _ = lax.scan(tick, carry, tables)
    grads = carry["grads"]
    if batch_axis is not None:
        # dp x pp composition: each data replica backpropagated only its
        # own batch shard — the stage grads must sum across replicas.
        # The legacy gpipe path gets this psum from shard_map's
        # transpose of the replicated param in_spec; the hand-written
        # backward inserts (and accounts) it explicitly.
        _account("psum", grads, batch_axis)
        grads = lax.psum(grads, batch_axis)
    dx = _ring_broadcast(carry["dx"], 0, axis_name,
                         schedule=schedule, hop="grad_input_broadcast")
    return grads, dx


# ------------------------------------------------------------- public API
def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "stage",
    n_microbatches: Optional[int] = None,
    batch_axis: str = "data",
    remat: bool = False,
    schedule: str = "gpipe",
    n_virtual: int = 1,
) -> jax.Array:
    """Run ``x`` through the stacked stages sequentially, pipelined.

    ``stage_fn(params_for_one_stage, microbatch) -> microbatch_out`` must
    preserve the activation shape (classic equal-width pipeline).
    ``stage_params``: pytree whose leaves have leading dim
    ``n_stages_total = mesh.shape[axis_name] * n_virtual`` (see
    ``stack_stage_params``).  ``x``: [batch, ...] — split into
    ``n_microbatches`` equal microbatches (default: one per stage).
    Semantically equivalent to folding ``stage_fn`` serially; every
    schedule only changes WHERE each stage runs and WHEN.

    ``schedule``: one of ``SCHEDULES`` (module docstring).  ``n_virtual``
    (``interleaved`` only): virtual stages per device — stage ``g`` lives
    on device ``g % S``, so hops stride the stage ring.

    ``remat=True`` recomputes stage bodies in the backward pass instead
    of storing activations: for ``gpipe`` via ``jax.checkpoint`` on the
    scan body; for the engine schedules via the combined backward table
    whose in-flight stash is bounded at ~S microbatches.  Math is
    unchanged either way.

    When the mesh also has a live ``batch_axis`` (dp × pp), each
    microbatch's batch dim shards over it — the data-parallel replicas
    pipeline their own slices and the gradient psum over ``data`` happens
    outside, exactly as with any other sharded batch.
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown schedule {schedule!r}; expected one of {SCHEDULES}"
        )
    if n_virtual < 1:
        raise ValueError(f"n_virtual must be >= 1, got {n_virtual}")
    if n_virtual > 1 and schedule != "interleaved":
        raise ValueError(
            "n_virtual > 1 is the interleaved schedule's knob; pass "
            f"schedule='interleaved' (got schedule={schedule!r})"
        )
    n_dev = mesh.shape[axis_name]
    n_total = n_dev * n_virtual
    leaves = jax.tree.leaves(stage_params)
    bad = [l.shape for l in leaves if l.ndim < 1 or l.shape[0] != n_total]
    if bad:
        raise ValueError(
            f"stage_params leaves must carry a leading stage dim of "
            f"{n_total} (= {n_dev} devices x {n_virtual} virtual); got "
            f"leading dims {sorted({s[0] if s else None for s in bad})}"
        )
    n_micro = n_microbatches or n_total
    if n_micro < n_total:
        raise ValueError(
            f"n_microbatches={n_micro} < n_stages={n_total}: every "
            "schedule here needs a full ramp (GPipe's bubble degenerates "
            "and 1F1B's in-flight stash sizing assumes M >= S); raise "
            "n_microbatches or lower the stage count"
        )
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(
            f"batch {batch} not divisible into {n_micro} microbatches"
        )
    if batch_axis is not None and batch_axis not in mesh.axis_names:
        batch_axis = None
    xm = x.reshape((n_micro, batch // n_micro) + x.shape[1:])
    x_spec = P(None, batch_axis) if batch_axis else P()
    p_specs = jax.tree.map(lambda _: P(axis_name), stage_params)

    if schedule == "gpipe":
        t_g = n_micro + n_dev - 1
        # Executed-compute waste (units: forward=1, backward-proper=2,
        # relinearize/recompute=1): the GPipe scan computes on EVERY
        # device EVERY tick — ramp slots execute garbage rather than
        # idling — and its autodiff backward replays all ticks (plus a
        # full recompute under remat).
        executed = n_dev * t_g * (1.0 + (3.0 if remat else 2.0))
        useful = 3.0 * n_micro * n_total
        _record_info("gpipe", {
            "schedule": "gpipe", "n_devices": n_dev, "n_virtual": 1,
            "n_stages": n_total, "n_micro": n_micro, "remat": bool(remat),
            "fwd_ticks": t_g,
            "bwd_ticks": t_g,
            # Classic ramp bubble, identical in the autodiff-mirrored
            # backward pass (no idle skipping in either).
            "bubble_fraction": round((n_dev - 1) / t_g, 4),
            "wasted_compute_fraction": round(1.0 - useful / executed, 4),
        })
        fn = shard_map(
            functools.partial(
                _pipeline_local,
                stage_fn=stage_fn,
                axis_name=axis_name,
                n_micro=n_micro,
                remat=remat,
            ),
            mesh=mesh,
            in_specs=(p_specs, x_spec),
            out_specs=x_spec,
            check_vma=False,
        )
        out = fn(stage_params, xm)
        return out.reshape((batch,) + out.shape[2:])

    # ------------------------------------------------ tick-table engine
    fwd_tabs = _build_tables(schedule, n_dev, n_virtual, n_micro, "fwd")
    bwd_mode = "bwd_recompute" if remat else "bwd_stash"
    bwd_tabs = _build_tables(schedule, n_dev, n_virtual, n_micro, bwd_mode)
    total_slots = (fwd_tabs.n_ticks + bwd_tabs.n_ticks) * n_dev
    busy = fwd_tabs.n_actions + bwd_tabs.n_actions
    # Executed-compute waste (same unit model as gpipe's): idle slots are
    # genuinely SKIPPED by the engine (lax.switch), so only scheduled
    # actions execute — a fused backward costs 3 units (1 relinearize +
    # 2 backward-proper), the zb halves 2 each.
    executed = (
        fwd_tabs.n_f + bwd_tabs.n_f
        + (2.0 * bwd_tabs.n_b + 2.0 * bwd_tabs.n_w if schedule == "zb"
           else 3.0 * bwd_tabs.n_b)
    )
    useful = 3.0 * n_micro * n_total
    _record_info(schedule, {
        "schedule": schedule, "n_devices": n_dev, "n_virtual": n_virtual,
        "n_stages": n_total, "n_micro": n_micro, "remat": bool(remat),
        "fwd_ticks": fwd_tabs.n_ticks, "bwd_ticks": bwd_tabs.n_ticks,
        "fwd_idle_fraction": round(fwd_tabs.idle_fraction, 4),
        "bwd_idle_fraction": round(bwd_tabs.idle_fraction, 4),
        "bubble_fraction": round(1.0 - busy / total_slots, 4),
        "wasted_compute_fraction": round(1.0 - useful / executed, 4),
        "stash_slots": bwd_tabs.n_f_slots if remat else None,
        "boundary_stash_microbatches": None if remat else n_micro,
    })

    if n_virtual > 1:
        # Round-robin placement: device d owns global stages {v*S + d}.
        # shard_map splits the leading dim contiguously, so permute the
        # stack to [stages of dev 0 | stages of dev 1 | ...] first; the
        # take's transpose un-permutes the grads automatically.
        perm = np.asarray(
            [v * n_dev + d for d in range(n_dev) for v in range(n_virtual)],
            np.int32,
        )
        p_sched = jax.tree.map(
            lambda p: jnp.take(p, perm, axis=0), stage_params
        )
    else:
        p_sched = stage_params

    stash_spec = (
        P(axis_name, None, batch_axis) if batch_axis else P(axis_name)
    )

    fwd_shard = shard_map(
        functools.partial(
            _engine_fwd_local,
            stage_fn=stage_fn, axis_name=axis_name,
            tables=fwd_tabs.as_jnp(), n_f_slots=fwd_tabs.n_f_slots,
            n_ticks=fwd_tabs.n_ticks, n_virtual=n_virtual,
            want_stash=not remat, schedule=schedule,
        ),
        mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=(x_spec, stash_spec) if not remat else (x_spec,),
        check_vma=False,
    )
    bwd_kwargs = dict(
        stage_fn=stage_fn, axis_name=axis_name,
        tables=bwd_tabs.as_jnp(), n_f_slots=bwd_tabs.n_f_slots,
        n_b_slots=bwd_tabs.n_b_slots, n_ticks=bwd_tabs.n_ticks,
        recompute=remat, schedule=schedule, batch_axis=batch_axis,
    )
    if remat:
        def _bwd_body(p, xx, dy):
            return _engine_bwd_local(p, xx, None, dy, **bwd_kwargs)

        bwd_shard = shard_map(
            _bwd_body,
            mesh=mesh,
            in_specs=(p_specs, x_spec, x_spec),
            out_specs=(p_specs, x_spec),
            check_vma=False,
        )
    else:
        def _bwd_body(p, xx, stash, dy):
            return _engine_bwd_local(p, xx, stash, dy, **bwd_kwargs)

        bwd_shard = shard_map(
            _bwd_body,
            mesh=mesh,
            in_specs=(p_specs, x_spec, stash_spec, x_spec),
            out_specs=(p_specs, x_spec),
            check_vma=False,
        )

    @jax.custom_vjp
    def _engine(p, xx):
        return fwd_shard(p, xx)[0]

    if remat:
        def _engine_fwd(p, xx):
            (y,) = fwd_shard(p, xx)
            return y, (p, xx)

        def _engine_bwd(res, dy):
            p, xx = res
            return bwd_shard(p, xx, dy)
    else:
        def _engine_fwd(p, xx):
            y, stash = fwd_shard(p, xx)
            return y, (p, xx, stash)

        def _engine_bwd(res, dy):
            p, xx, stash = res
            return bwd_shard(p, xx, stash, dy)

    _engine.defvjp(_engine_fwd, _engine_bwd)
    out = _engine(p_sched, xm)
    return out.reshape((batch,) + out.shape[2:])
