"""Benchmark harness — one JSON line for the driver.

Metric: training throughput (samples/sec) of the reference parity workload —
MLModel (LeNet) on CIFAR-10-shaped data at global batch 32, full train step
(forward, loss, backward, SGD update + on-device metric), driven through the
framework's Trainer machinery (prefetched Loader + compiled step), i.e. the
exact configuration behind the reference's only recorded number:
822–966 samples/s on local CPU (01 nb cell-12; BASELINE.md).  ``vs_baseline``
divides by the best reference figure (966).

Run ``python bench.py --extended`` for the north-star model table
(ResNet-50, ViT-B/16, BERT-base, GPT-2-124M step throughput) printed as
extra human-readable lines before the JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ml_trainer_tpu.trainer import enable_compilation_cache
from ml_trainer_tpu.utils.profiler import StepTimer

BASELINE_SAMPLES_PER_SEC = 966.0  # reference train throughput, BASELINE.md


def _utcnow() -> str:
    """HH:MM:SSZ stamp for the artifacts the CPU legs write."""
    return time.strftime("%H:%M:%S", time.gmtime()) + "Z"


def _device_stamp() -> dict:
    """What every printed record names: the device it was measured on,
    as JAX reports it."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def _require_chip(cpu: bool) -> None:
    """The measurement legs (default row, ``--one``, ``--extended``) FAIL
    without a chip: there is no CPU fallback, so a CPU number can never
    land under a device metric's name.  ``--cpu`` is the explicit,
    labelled host run."""
    if cpu:
        return
    stamp = _device_stamp()
    if stamp["platform"] != "tpu":
        sys.exit(
            f"bench.py: no TPU attached (platform={stamp['platform']}, "
            f"device_kind={stamp['device_kind']}); this leg measures the "
            "chip and has no fallback — pass --cpu for an explicitly "
            "labelled host run"
        )


def _steady_state_rate(step, state, batches, warmup=5, iters=50):
    """Steps/sec via the fenced StepTimer (compile/warmup excluded)."""
    timer = StepTimer(warmup=warmup)
    for i in range(warmup + iters):
        state, *_ = step(state, *batches[i % len(batches)])
        timer.tick(state, 1)
    return timer.rate(), state


PARITY_DS_SIZE = 8192  # synthetic dataset behind bench_parity

# Default K: the parity workload is dispatch-bound (a 62K-param LeNet step
# executes in microseconds; every dispatch pays a host->device round
# trip), so throughput scales with K until the chained execution dwarfs
# the round trip.  Trajectory is identical to per-batch stepping
# regardless of K (tests/test_trainer.py).
PARITY_K = 128


def _effective_k(batch_size: int, steps_per_execution: int = PARITY_K) -> int:
    """The multi-step K bench_parity will actually use — large batches
    leave too few batches per epoch and clamp K down to 1."""
    return max(
        1, min(steps_per_execution, PARITY_DS_SIZE // batch_size // 2)
    )


def bench_parity(batch_size=32, steps_per_execution=PARITY_K):
    """The reference workload through the real Trainer train step.

    Uses the Trainer's multi-step fast path (``steps_per_execution`` K
    optimizer steps per dispatch via lax.scan — trajectory identical to
    per-batch stepping, verified in tests/test_trainer.py) so the number
    reflects the chip, not Python dispatch."""
    from ml_trainer_tpu import Trainer, MLModel
    from ml_trainer_tpu.data import SyntheticCIFAR10
    from ml_trainer_tpu.utils.functions import custom_pre_process_function

    ds = SyntheticCIFAR10(
        size=PARITY_DS_SIZE, transform=custom_pre_process_function()
    )
    # Large batch sizes leave few batches per epoch: cap K so at least one
    # full stack exists, falling back to the per-batch path at K=1.
    k = _effective_k(batch_size, steps_per_execution)
    trainer = Trainer(
        MLModel(), datasets=(ds, ds), epochs=1, batch_size=batch_size,
        model_dir="/tmp/bench_model", metric="accuracy", lr=0.01,
        steps_per_execution=k,
    )
    # Pre-materialize transformed, stacked device batches so we measure the
    # compiled program (the input pipeline overlaps via prefetch during real
    # training).
    from ml_trainer_tpu.data import prefetch_to_device

    if k == 1:
        batches = [
            (x, y, jnp.asarray(1.0, jnp.float32))
            for _, (x, y) in zip(
                range(16),
                prefetch_to_device(
                    trainer.train_loader, size=2,
                    sharding=trainer._batch_sharding,
                ),
            )
        ]
        rate, _ = _steady_state_rate(trainer._train_step, trainer.state, batches)
        return rate * batch_size
    raw = [b for _, b in zip(range(2 * k), trainer.train_loader)]
    stacked = [
        tuple(np.stack(t) for t in zip(*raw[i * k:(i + 1) * k]))
        for i in range(len(raw) // k)
    ]
    batches = [
        (xs, ys, jnp.asarray(1.0, jnp.float32))
        for xs, ys in prefetch_to_device(
            iter(stacked), size=2, sharding=trainer._stacked_sharding
        )
    ]
    rate, _ = _steady_state_rate(
        trainer._train_multi_step, trainer.state, batches, warmup=2, iters=8
    )
    return rate * batch_size * k


def bench_loaders(size=4096, batch_size=256, epochs=4):
    """Host input-pipeline throughput: Python Loader vs native C++ worker,
    same fused augmentation (crop/flip/normalize)."""
    from ml_trainer_tpu.data import Loader, SyntheticCIFAR10
    from ml_trainer_tpu.data.native import NativeLoader, native_available
    from ml_trainer_tpu.utils.functions import custom_pre_process_function

    ds = SyntheticCIFAR10(size=size, transform=custom_pre_process_function())

    def rate(loader):
        list(loader)  # warm (build lib / allocate)
        t0 = time.perf_counter()
        n = 0
        for _ in range(epochs):
            for x, _y in loader:
                n += x.shape[0]
        return n / (time.perf_counter() - t0)

    py = rate(Loader(ds, batch_size=batch_size, shuffle=True, seed=0))
    print(f"# input pipeline python: {py:,.0f} samples/s")
    if native_available():
        nat = rate(NativeLoader(ds, batch_size=batch_size, seed=0))
        print(
            f"# input pipeline native (C++): {nat:,.0f} samples/s "
            f"({nat / py:.2f}x python)"
        )
    else:
        print("# input pipeline native (C++): unavailable on this host")


def bench_serve(n_requests=32, mean_interarrival=0.01, max_batch=8,
                seed=0):
    """Serving leg: the continuous-batching engine vs a dynamic-batching
    ``generate_ragged`` baseline on the SAME ragged Poisson arrival trace.

    The workload is serving-shaped: ragged prompt lengths, ragged
    per-request token budgets (most requests short, a heavy tail long —
    the distribution that makes one-shot batching convoy), Poisson
    arrivals (real sleeps on a compressed timescale).  The baseline is
    the strongest server one can write on the one-shot API: harvest
    everything queued at each completion boundary and run it through
    ``generate_ragged`` (length buckets, pow2 batch padding) decoded to
    the harvested batch's LARGEST budget — short requests ride out the
    longest one (the convoy), and late arrivals wait for the whole
    batch.  The engine admits each request into a slot at the next token
    boundary and frees the slot the moment its budget is spent.  Both
    paths are warmed over the workload's compile shapes first, count
    only USEFUL tokens (each request's own budget), and are timed from
    first submission to last completion.
    Returns {"engine_tokens_per_sec", "baseline_tokens_per_sec", ...}.
    """
    import queue as _queue
    import threading

    from ml_trainer_tpu.generate import generate, generate_ragged
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import Server

    model = get_model("gpt2_tiny", max_len=128)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    rng = np.random.default_rng(seed)
    # Few distinct lengths/budgets (still ragged): keeps the baseline's
    # (length, batch, max_new) compile space warmable so the measured
    # gap is scheduling, not XLA compilation.
    lengths = rng.choice([5, 9], size=n_requests)
    budgets = rng.choice([4, 64], size=n_requests, p=[0.75, 0.25])
    prompts = [
        rng.integers(0, model.vocab_size, size=l).astype(np.int32)
        for l in lengths
    ]
    arrivals = np.cumsum(rng.exponential(mean_interarrival, n_requests))
    total_tokens = int(budgets.sum())  # useful tokens, both paths

    def run_engine():
        with Server(model, variables, max_batch=max_batch,
                    max_queue=n_requests) as srv:
            # Warm the engine's compiled programs (prefill buckets +
            # decode step) outside the timed window.
            for l in sorted(set(int(x) for x in lengths)):
                srv.complete(prompts[list(lengths).index(l)], 2,
                             timeout=300)
            t0 = time.perf_counter()
            streams = []
            for i, p in enumerate(prompts):
                wait = arrivals[i] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
                streams.append(srv.submit(p, int(budgets[i])))
            lat = []
            for i, s in enumerate(streams):
                s.result(timeout=600)
                lat.append(
                    s.request.finished_at - s.request.submitted_at
                )
            elapsed = time.perf_counter() - t0
        return total_tokens / elapsed, float(np.median(lat))

    def run_baseline():
        # Warm every (length, pow2-batch<=max_batch, batch-max-budget)
        # program the harvest loop can hit.
        for l in sorted(set(int(x) for x in lengths)):
            p = prompts[list(lengths).index(l)]
            for m in sorted(set(int(x) for x in budgets)):
                b = 1
                while b <= max_batch:
                    generate(model, variables, np.stack([p] * b), m)
                    b *= 2
        pending: _queue.Queue = _queue.Queue()

        def feeder():
            t0 = time.perf_counter()
            for i, p in enumerate(prompts):
                wait = arrivals[i] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
                pending.put((i, p, time.perf_counter()))

        th = threading.Thread(target=feeder)
        t0 = time.perf_counter()
        th.start()
        done, lat = 0, []
        while done < n_requests:
            batch = [pending.get()]
            while len(batch) < max_batch:
                try:
                    batch.append(pending.get_nowait())
                except _queue.Empty:
                    break
            # One-shot API: the whole batch decodes to its largest
            # budget (per-request early exit is exactly what the API
            # cannot do); surplus tokens are discarded, not counted.
            horizon = max(int(budgets[i]) for i, _, _ in batch)
            generate_ragged(
                model, variables, [p for _, p, _ in batch], horizon
            )
            now = time.perf_counter()
            lat.extend(now - t_in for _, _, t_in in batch)
            done += len(batch)
        elapsed = time.perf_counter() - t0
        th.join()
        return total_tokens / elapsed, float(np.median(lat))

    base_tps, base_lat = run_baseline()
    print(f"# serve baseline (generate_ragged): {base_tps:,.1f} tokens/s, "
          f"p50 latency {base_lat * 1e3:,.0f} ms", flush=True)
    eng_tps, eng_lat = run_engine()
    print(f"# serve engine (continuous batching): {eng_tps:,.1f} tokens/s, "
          f"p50 latency {eng_lat * 1e3:,.0f} ms "
          f"({eng_tps / base_tps:.2f}x baseline)", flush=True)
    return {
        "engine_tokens_per_sec": round(eng_tps, 1),
        "baseline_tokens_per_sec": round(base_tps, 1),
        "engine_p50_latency_ms": round(eng_lat * 1e3, 1),
        "baseline_p50_latency_ms": round(base_lat * 1e3, 1),
        "speedup": round(eng_tps / base_tps, 2),
        "n_requests": n_requests,
        "useful_tokens": total_tokens,
        "backend": jax.default_backend(),
    }


def bench_serve_replay(n_requests=48, n_tenants=3, shared_frac=0.8,
                       mean_interarrival=0.002, max_batch=8, seed=0,
                       page_size=16, shared_len=160, out_path=None,
                       spec_check=True):
    """Multi-tenant ragged replay: PAGED engine (page pool + radix
    prefix cache + tenant scheduler) vs the CONTIGUOUS engine on the
    same trace.

    The trace is production-shaped serving traffic: ``n_tenants``
    tenants with Poisson arrivals, ``shared_frac`` of each tenant's
    requests opening with that tenant's long shared prefix (system
    prompt / few-shot preamble — ``shared_len`` tokens) followed by a
    short unique suffix, the rest fully unique; ragged budgets with a
    heavy tail.  Both engines replay the identical submissions
    (prompt, budget, tenant, arrival time).

    Method: each engine runs the trace TWICE and the second pass is
    timed — pass 1 warms every compiled shape AND fills the prefix
    cache to steady state, and the compiled-program count is asserted
    constant across the timed pass (the zero-recompile pin).  Greedy
    outputs are asserted byte-identical between the two engines, and
    (``spec_check``) a spec_k mini-replay is asserted identical too.
    Reports sustained tokens/s (useful generated tokens over makespan)
    and TTFT p50/p99.  ``out_path`` writes the JSON artifact
    (docs/serving_replay_cpu.json is the committed copy gated by
    scripts/bench_gate.py).
    """
    from ml_trainer_tpu.generate import _COMPILED, generate
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import Server, TenantConfig

    model = get_model("gpt2_tiny", max_len=256)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    rng = np.random.default_rng(seed)
    tenants = {
        f"tenant{t}": TenantConfig(weight=float(t + 1))
        for t in range(n_tenants)
    }
    prefixes = [
        rng.integers(0, model.vocab_size, shared_len).astype(np.int32)
        for _ in range(n_tenants)
    ]
    trace = []
    for i in range(n_requests):
        t = int(rng.integers(0, n_tenants))
        if rng.random() < shared_frac:
            suffix = rng.integers(
                0, model.vocab_size, int(rng.integers(4, 17))
            ).astype(np.int32)
            prompt = np.concatenate([prefixes[t], suffix])
        else:
            prompt = rng.integers(
                0, model.vocab_size, int(rng.integers(16, 33))
            ).astype(np.int32)
        budget = int(rng.choice([4, 16], p=[0.75, 0.25]))
        trace.append((prompt, budget, f"tenant{t}"))
    arrivals = np.cumsum(rng.exponential(mean_interarrival, n_requests))
    useful_tokens = sum(b for _, b, _ in trace)

    def replay(server, timed: bool):
        t0 = time.perf_counter()
        streams = []
        for i, (prompt, budget, tenant) in enumerate(trace):
            wait = arrivals[i] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            streams.append(server.submit(prompt, budget, tenant=tenant))
        outs, ttfts = [], []
        for s in streams:
            outs.append(np.asarray(s.result(timeout=600)))
            ttfts.append(s.request.first_token_at - s.request.submitted_at)
        makespan = time.perf_counter() - t0
        ttfts = np.sort(np.asarray(ttfts))
        return {
            "tokens_per_sec": round(useful_tokens / makespan, 1),
            "ttft_p50_ms": round(float(ttfts[len(ttfts) // 2]) * 1e3, 1),
            "ttft_p99_ms": round(
                float(ttfts[min(len(ttfts) - 1,
                                int(0.99 * (len(ttfts) - 1) + 0.5))]) * 1e3,
                1,
            ),
            "makespan_s": round(makespan, 3),
        }, outs

    def run_engine(paged: bool):
        kwargs = dict(max_batch=max_batch, max_queue=n_requests,
                      tenants=dict(tenants))
        if paged:
            kwargs.update(kv_page_size=page_size)
        with Server(model, variables, **kwargs) as srv:
            replay(srv, timed=False)          # warm compiles + prefix cache
            n_warm = len(_COMPILED._data)
            stats, outs = replay(srv, timed=True)
            n_after = len(_COMPILED._data)
            snap = srv.metrics.snapshot()
        stats["compiled_programs_constant"] = n_after == n_warm
        stats["prefix_hit_rate"] = snap["prefix_hit_rate"]
        stats["preemptions"] = snap["preemptions_total"]
        return stats, outs

    contig, contig_outs = run_engine(paged=False)
    print(f"# serve replay contiguous: {contig['tokens_per_sec']:,.1f} "
          f"tokens/s, TTFT p99 {contig['ttft_p99_ms']:,.1f} ms", flush=True)
    paged, paged_outs = run_engine(paged=True)
    print(f"# serve replay paged:      {paged['tokens_per_sec']:,.1f} "
          f"tokens/s, TTFT p99 {paged['ttft_p99_ms']:,.1f} ms "
          f"({paged['tokens_per_sec'] / contig['tokens_per_sec']:.2f}x, "
          f"prefix hit rate {paged['prefix_hit_rate']:.2f})", flush=True)

    identical = all(
        np.array_equal(a, b) for a, b in zip(contig_outs, paged_outs)
    )
    spec_identical = None
    if spec_check:
        # Spec mini-replay: the fixed-K verify window reading through
        # page tables must still be byte-identical to the contiguous
        # spec path (and to generate()).
        mini = trace[: min(6, len(trace))]
        refs = [
            np.asarray(generate(model, variables, p[None], b))[0]
            for p, b, _ in mini
        ]
        spec_outs = {}
        for paged_flag in (False, True):
            kwargs = dict(max_batch=4, max_queue=len(mini), spec_k=4)
            if paged_flag:
                kwargs.update(kv_page_size=page_size)
            with Server(model, variables, **kwargs) as srv:
                ss = [srv.submit(p, b, tenant=t) for p, b, t in mini]
                spec_outs[paged_flag] = [
                    np.asarray(s.result(timeout=600)) for s in ss
                ]
        spec_identical = all(
            np.array_equal(a, b) and np.array_equal(a, r)
            for a, b, r in zip(spec_outs[False], spec_outs[True], refs)
        )
    result = {
        "paged": paged,
        "contiguous": contig,
        "speedup": round(
            paged["tokens_per_sec"] / contig["tokens_per_sec"], 3
        ),
        "ttft_p99_ratio": round(
            paged["ttft_p99_ms"] / contig["ttft_p99_ms"], 3
        ) if contig["ttft_p99_ms"] else None,
        "greedy_byte_identical": identical,
        "spec_byte_identical": spec_identical,
        "n_requests": n_requests,
        "n_tenants": n_tenants,
        "shared_frac": shared_frac,
        "shared_len": shared_len,
        "page_size": page_size,
        "max_batch": max_batch,
        "useful_tokens": useful_tokens,
        "backend": jax.default_backend(),
    }
    if not identical:
        result["error"] = "paged output diverged from contiguous"
    if spec_identical is False:
        result["error"] = "spec paged output diverged"
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# serve replay artifact -> {out_path}", flush=True)
    return result


def bench_slo(rates=(40.0, 120.0, 360.0, 720.0), n_requests=36, seed=0,
              ttft_ms=50.0, tpot_ms=25.0, max_batch=8, page_size=16,
              out_path=None, target_url=None):
    """Open-loop SLO sweep (docs/observability.md "Serving SLO"): fixed
    Poisson arrival schedules at ``rates`` offered req/s drive the REAL
    HTTP server end to end (POST /v1/generate per request), and each
    rate reports TTFT / TPOT / queue-wait / e2e p50+p99 with SLO
    attainment and burn rate — the capacity-vs-SLO curve the autoscaler
    and disaggregation work will be judged with.

    Method guards:

    * **Open loop.**  Every schedule is fixed before its run (seeded
      Poisson arrivals, per-tenant prompt/output mixes, shared
      prefixes); requests fire at their absolute scheduled instant
      whether or not earlier ones completed — queueing under overload
      lands in the latencies instead of vanishing into a coordinated-
      omission feedback loop.
    * **Steady state.**  Each rate's schedule runs twice UNTIMED first
      (pass 1 mints every compiled shape and fills the prefix cache;
      pass 2 reaches the steady-state hit pattern whose continuation
      buckets the timed pass will use), then once timed.
    * **Zero recompiles.**  The timed pass runs under
      ``compile_watch.expect_no_compiles`` — a compile mid-measurement
      invalidates the row and fails the artifact.
    * **Server-side truth.**  Latencies come from the request-lifecycle
      timelines (``SloTracker``), scoped to the timed window; the
      client-observed e2e and scheduling fidelity (send lag) ride
      alongside from the load generator.

    ``target_url`` points the SAME schedules at an EXTERNAL target —
    a single replica's front end or the disaggregated router's
    (``bench.py --slo-url http://host:port``) — instead of building a
    local server; rows then carry the client-side aggregation only
    (no in-process timeline access).
    """
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import (
        Server, SloPolicy, TenantConfig, TenantLoad, poisson_schedule,
        run_open_loop,
    )
    from ml_trainer_tpu.serving.slo import aggregate_timelines
    from ml_trainer_tpu.telemetry import compile_watch

    model = get_model("gpt2_tiny", max_len=256)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    policy = SloPolicy(ttft_ms=ttft_ms, tpot_ms=tpot_ms, target=0.9)
    # Production-shaped mix: a heavier "pro" tenant whose requests open
    # with a shared system prompt (prefix-cache reuse), a lighter fully
    # unique "free" tenant.
    load = {
        "pro": TenantLoad(weight=2.0, prompt_len=(8, 24),
                          output_len=(4, 16), shared_prefix_len=32,
                          shared_frac=0.6),
        "free": TenantLoad(weight=1.0, prompt_len=(8, 24),
                           output_len=(4, 16)),
    }
    tenant_cfg = {"pro": TenantConfig(weight=2.0),
                  "free": TenantConfig(weight=1.0)}
    compile_watch.install()
    rows = []
    for i, rate in enumerate(rates):
        schedule = poisson_schedule(
            float(rate), n_requests, model.vocab_size, tenants=load,
            seed=seed + i,
        )
        if target_url is not None:
            # External target (single replica or router): same recorded
            # schedule, client-side truth only.
            for _ in range(2):
                run_open_loop(schedule, url=target_url, time_scale=0.0)
            client = run_open_loop(schedule, url=target_url)
            client.pop("per_request")
            rows.append({
                "offered_rps": float(rate),
                "n_requests": n_requests,
                "tokens_per_sec": client["tokens_per_sec"],
                "n_errors": client["n_errors"],
                "client": client,
                "target_url": target_url,
                "zero_recompiles": True,  # not observable externally
            })
            print(
                f"# slo rate {rate:>6.1f} rps -> {target_url}: "
                f"{client['tokens_per_sec']:,.1f} tokens/s, client e2e "
                f"p99 {client['client_e2e_p99_ms']} ms",
                flush=True,
            )
            continue
        with Server(model, variables, max_batch=max_batch,
                    max_queue=2 * n_requests, kv_page_size=page_size,
                    tenants=dict(tenant_cfg), slo=policy,
                    slo_timelines=4 * n_requests) as srv:
            host, port = srv.serve_http(port=0)
            url = f"http://{host}:{port}"
            # Two untimed passes: compiles + prefix cache to steady
            # state (pass 2's hit pattern == the timed pass's).
            for _ in range(2):
                run_open_loop(schedule, url=url, time_scale=0.0)
            timed_t0 = time.monotonic()
            err = None
            try:
                with compile_watch.expect_no_compiles(f"slo rate {rate}"):
                    client = run_open_loop(schedule, url=url)
            except AssertionError as e:
                err = str(e)
                client = run_open_loop(schedule, url=url)
            server_side = aggregate_timelines(
                srv.slo.timelines(since=timed_t0), policy
            )
            snap = srv.metrics.snapshot()
        client.pop("per_request")
        row = {
            "offered_rps": float(rate),
            "n_requests": n_requests,
            "tokens_per_sec": client["tokens_per_sec"],
            "n_errors": client["n_errors"],
            "client": client,
            "server": server_side,
            "prefix_hit_rate": snap["prefix_hit_rate"],
            "preemptions": snap["preemptions_total"],
            "zero_recompiles": err is None,
        }
        if err is not None:
            row["recompile_error"] = err
        rows.append(row)
        print(
            f"# slo rate {rate:>6.1f} rps: {row['tokens_per_sec']:,.1f} "
            f"tokens/s, TTFT p99 {server_side['ttft_ms']['p99']} ms, "
            f"TPOT p99 {server_side['tpot_ms']['p99']} ms, attainment "
            f"ttft={server_side['attainment']['ttft']} "
            f"tpot={server_side['attainment']['tpot']}"
            + ("" if err is None else "  [RECOMPILED]"),
            flush=True,
        )
    result = {
        "policy": {"ttft_ms": ttft_ms, "tpot_ms": tpot_ms,
                   "target": policy.target},
        "rates": rows,
        "n_requests_per_rate": n_requests,
        "max_batch": max_batch,
        "page_size": page_size,
        "seed": seed,
        "zero_recompiles": all(r["zero_recompiles"] for r in rows),
        "backend": jax.default_backend(),
    }
    if not result["zero_recompiles"]:
        result["error"] = "compiles observed during a timed pass"
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# slo artifact -> {out_path}", flush=True)
    return result


def bench_serve_lora(n_adapters=64, n_requests=96, rate_rps=400.0,
                     max_batch=8, page_size=16, rank=8, seed=0,
                     out_path=None, target_url=None):
    """Batched-LoRA serving leg (docs/serving.md "Batched LoRA
    adapters"): ``n_adapters`` concurrent adapters over ONE gpt2 base,
    open-loop at saturating load through the real HTTP server, vs the
    single-model baseline on the identical schedule.

    Method guards:

    * **Identical traffic.**  One seeded Poisson schedule whose
      requests draw uniformly from {base, adapter_00..} plus a shared
      system prefix; the baseline server runs the SAME schedule with
      every adapter field stripped — so the ratio prices exactly the
      per-row gather + low-rank delta, not a workload difference.
    * **Byte identity.**  Every ``adapter=None`` request's output on
      the LoRA server must equal the baseline server's output for the
      same request (the trash-slot-0 zero-delta contract).
    * **Hot-load mid-run.**  A brand-new adapter registers and serves
      DURING the timed pass, inside ``compile_watch.expect_no_compiles``
      — the one warm upload program plus the rank bucket make the load
      a pure data movement.
    * **Mixed ranks.**  Adapters alternate trained rank ``rank/2`` and
      ``rank`` (zero-padded into the one bucket), so the zero-recompile
      pin covers the mixed-rank case.

    ``target_url`` points the same schedule at an EXTERNAL target
    (``bench.py --serve-lora-url http://host:port`` — e.g. a router
    fleet built with adapter pools); rows then carry client-side truth
    only and no artifact is written.
    """
    import os
    import tempfile

    from ml_trainer_tpu.lora import LoraConfig, export_lora_artifact
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import (
        AdapterConfig, Server, TenantLoad, poisson_schedule,
        run_open_loop,
    )
    from ml_trainer_tpu.telemetry import compile_watch

    # gpt2_mini (512-wide): wide enough that a rank-8 delta is the
    # production-shaped small fraction of the base matmul — on the
    # 128-wide test config the gather+delta is a third of the whole
    # step and the ratio measures the toy width, not the design.
    model = get_model("gpt2_mini", max_len=256)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    targets = ("qkv", "proj")
    names = [f"a{i:02d}" for i in range(n_adapters - 1)]

    # Fabricate adapter artifacts: train-mode init (A small, B zeros)
    # with B given real mass, alternating trained ranks — small enough
    # that tokens stay plausible, large enough that outputs differ.
    tmp = tempfile.mkdtemp(prefix="bench_lora_")
    rng = np.random.default_rng(seed)

    def make_artifact(name, r, scale=0.5):
        lm = model.clone(lora_rank=r, lora_alpha=float(2 * r),
                         lora_targets=targets)
        params = jax.device_get(lm.init(
            {"params": jax.random.PRNGKey(1)},
            np.zeros((1, 8), np.int32), train=False,
        )["params"])

        def bump(node):
            out = {}
            for k, v in node.items():
                if hasattr(v, "items"):
                    out[k] = bump(v)
                elif "_lora_B" in k:
                    out[k] = rng.standard_normal(
                        v.shape
                    ).astype(np.float32) * scale
                else:
                    out[k] = v
            return out

        path = os.path.join(tmp, f"{name}.npz")
        export_lora_artifact(
            bump(dict(params)),
            LoraConfig(rank=r, alpha=float(2 * r), targets=targets),
            path, name=name,
        )
        return path

    sources = {
        n: make_artifact(n, rank if i % 2 else rank // 2)
        for i, n in enumerate(names)
    }
    hot_path = make_artifact("hot", rank)

    # ~20% base traffic interleaved with the adapter mix; the first
    # len(names) arrivals are then pinned to cover EVERY adapter once,
    # so the pool genuinely holds n_adapters concurrent residents.
    # shared_frac is modest: per-adapter prefix namespacing (correct by
    # construction — K/V is adapter-specific) means 64-way traffic
    # cannot share the system prefix the way one model can, and the
    # ratio should price the GATHER, not mostly that hit-rate delta.
    mix = TenantLoad(
        weight=1.0, prompt_len=(8, 24), output_len=(4, 16),
        shared_prefix_len=16, shared_frac=0.25,
        adapters=(None,) * (len(names) // 4) + tuple(names),
    )
    schedule = poisson_schedule(
        float(rate_rps), n_requests, model.vocab_size,
        tenants={"mix": mix}, seed=seed,
    )
    import dataclasses as _dc

    schedule = [
        _dc.replace(s, adapter=names[i]) if i < len(names) else s
        for i, s in enumerate(schedule)
    ]
    base_schedule = [_dc.replace(s, adapter=None) for s in schedule]

    if target_url is not None:
        for _ in range(2):
            run_open_loop(schedule, url=target_url, time_scale=0.0)
        client = run_open_loop(schedule, url=target_url)
        client.pop("per_request")
        return {
            "target_url": target_url,
            "n_adapters": n_adapters,
            "tokens_per_sec": client["tokens_per_sec"],
            "n_errors": client["n_errors"],
            "client": client,
        }

    def serve(schedule_, srv):
        host, port = srv.serve_http(port=0)
        url = f"http://{host}:{port}"
        for _ in range(2):          # compiles + prefix cache + adapter
            run_open_loop(schedule_, url=url, time_scale=0.0)  # loads
        err = None
        hot_result = {}
        snap0 = srv.metrics.snapshot()

        def hot_load():
            # The hot-load protocol under live traffic: a NEVER-seen
            # adapter registers mid-pass and serves immediately.
            if srv.engine.adapters is None:
                return
            time.sleep(0.2)
            srv.load_adapter("hot", hot_path)
            p = np.asarray(schedule_[0].prompt, np.int32)
            out = srv.complete(p, 8, adapter="hot", timeout=300)
            hot_result["tokens"] = int(np.asarray(out).size - p.size)

        import threading

        try:
            with compile_watch.expect_no_compiles("lora timed pass"):
                hot = threading.Thread(target=hot_load, daemon=True)
                hot.start()
                client = run_open_loop(
                    schedule_, url=url, collect_tokens=True
                )
                hot.join(timeout=300)
        except AssertionError as e:
            err = str(e)
            client = run_open_loop(schedule_, url=url, collect_tokens=True)
        snap = srv.metrics.snapshot()
        # Device-busy tokens/s over the timed pass only (cumulative
        # counters, so delta vs the pre-pass snapshot): the engine-side
        # rate, far less noisy than client makespan on a shared
        # container — what the single-model ratio is judged on.
        d_tokens = snap["tokens_total"] - snap0["tokens_total"]
        busy0 = (
            snap0["tokens_total"] / snap0["tokens_per_sec_busy"]
            if snap0["tokens_per_sec_busy"] else 0.0
        )
        busy1 = (
            snap["tokens_total"] / snap["tokens_per_sec_busy"]
            if snap["tokens_per_sec_busy"] else 0.0
        )
        snap["timed_tokens_per_sec_busy"] = round(
            d_tokens / (busy1 - busy0), 1
        ) if busy1 > busy0 else 0.0
        return client, snap, err, hot_result

    compile_watch.install()
    with Server(model, variables, max_batch=max_batch,
                max_queue=2 * n_requests, kv_page_size=page_size) as srv:
        base_client, base_snap, base_err, _ = serve(base_schedule, srv)
    print(
        f"# serve lora single-model baseline: "
        f"{base_client['tokens_per_sec']:,.1f} tokens/s", flush=True,
    )
    with Server(model, variables, max_batch=max_batch,
                max_queue=2 * n_requests, kv_page_size=page_size,
                adapters=AdapterConfig(
                    slots=n_adapters + 2, rank=rank, targets=targets,
                    sources=sources,
                )) as srv:
        lora_client, lora_snap, lora_err, hot_result = serve(
            schedule, srv
        )
        resident = srv.health()["adapters_resident"]
    ratio = (
        lora_snap["timed_tokens_per_sec_busy"]
        / base_snap["timed_tokens_per_sec_busy"]
        if base_snap["timed_tokens_per_sec_busy"] else 0.0
    )
    print(
        f"# serve lora {n_adapters} adapters:       "
        f"{lora_snap['timed_tokens_per_sec_busy']:,.1f} busy tokens/s "
        f"vs {base_snap['timed_tokens_per_sec_busy']:,.1f} single-model "
        f"({ratio:.2f}x), {len(resident)} resident, hot-load "
        f"{'ok' if hot_result.get('tokens') else 'MISSING'}", flush=True,
    )

    # Byte identity: every adapter=None request equal across servers.
    identical = True
    n_base_rows = 0
    for s, lr, br in zip(schedule, lora_client["per_request"],
                         base_client["per_request"]):
        if s.adapter is not None:
            continue
        n_base_rows += 1
        if lr.get("output") != br.get("output"):
            identical = False
    result = {
        "n_adapters": n_adapters,
        "adapters_resident": len(resident),
        "rank_bucket": rank,
        "mixed_ranks": [rank // 2, rank],
        "targets": list(targets),
        "n_requests": n_requests,
        "offered_rps": float(rate_rps),
        "lora": {
            "tokens_per_sec": lora_client["tokens_per_sec"],
            "tokens_per_sec_busy": lora_snap["timed_tokens_per_sec_busy"],
            "client_e2e_p99_ms": lora_client["client_e2e_p99_ms"],
            "n_errors": lora_client["n_errors"],
            "adapter_hits": lora_snap["adapter_hits_total"],
            "adapter_loads": lora_snap["adapter_loads_total"],
            "adapter_evictions": lora_snap["adapter_evictions_total"],
            "adapter_pool_bytes": lora_snap["adapter_pool_bytes"],
            "prefix_hit_rate": lora_snap["prefix_hit_rate"],
        },
        "single_model": {
            "tokens_per_sec": base_client["tokens_per_sec"],
            "tokens_per_sec_busy": base_snap["timed_tokens_per_sec_busy"],
            "client_e2e_p99_ms": base_client["client_e2e_p99_ms"],
            "n_errors": base_client["n_errors"],
            "prefix_hit_rate": base_snap["prefix_hit_rate"],
        },
        "tokens_per_sec_ratio": round(ratio, 3),
        "base_requests_byte_identical": identical,
        "n_base_requests_compared": n_base_rows,
        "hot_load_tokens": hot_result.get("tokens", 0),
        "zero_recompiles": lora_err is None and base_err is None,
        "backend": jax.default_backend(),
    }
    if lora_err or base_err:
        result["recompile_error"] = lora_err or base_err
    if not identical:
        result["error"] = "adapter=None output diverged from single-model"
    elif not result["zero_recompiles"]:
        result["error"] = "compiles observed during a timed pass"
    elif not hot_result.get("tokens"):
        result["error"] = "mid-run hot-load did not serve"
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# serve lora artifact -> {out_path}", flush=True)
    return result


def bench_serve_disagg(n_requests=48, n_tenants=3, shared_frac=0.8,
                       mean_interarrival=0.002, shared_len=160,
                       page_size=16, max_batch=4, n_prefill=2,
                       n_decode=2, seed=0, ttft_ms=1000.0,
                       tpot_ms=1000.0, pool_factor=3, out_path=None):
    """Disaggregated prefill/decode serving vs colocated at EQUAL
    replica count (serving/router.py, docs/serving.md): the same
    recorded 80%-shared-prefix trace, replayed open-loop at saturating
    load through each topology's ROUTER HTTP front end.

    * **Disaggregated**: ``n_prefill`` prefill + ``n_decode`` decode
      replicas; every request prefills on an affinity-hashed prefill
      replica, its KV migrates at page granularity to the least-loaded
      decode replica.  Prefill slots turn over in one prefill's time,
      so TTFT stops queueing behind other requests' decode residency —
      the p99 TTFT win this artifact pins.
    * **Colocated**: ``n_prefill + n_decode`` replicas serving both
      roles behind the same router (no migration) — the equal-count
      baseline.

    Method guards (the bench_slo discipline): the trace is FIXED before
    any run (seeded, round-tripped through the recorded-trace format so
    both topologies replay identical bytes), each topology runs the
    trace twice untimed (compiles incl. the kv export/import programs +
    prefix caches to steady state) then once timed under
    ``compile_watch.expect_no_compiles``; TTFT truth comes from the
    ROUTER's request-lifecycle timelines scoped to the timed window;
    and every request's full output ids are collected and compared
    between topologies — zero byte-identity regressions is a hard
    invariant of the artifact."""
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import Router, SloPolicy
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )
    from ml_trainer_tpu.serving.slo import aggregate_timelines
    from ml_trainer_tpu.telemetry import compile_watch

    model = get_model("gpt2_tiny", max_len=256)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    rng = np.random.default_rng(seed)
    prefixes = [
        rng.integers(0, model.vocab_size, shared_len).astype(np.int32)
        for _ in range(n_tenants)
    ]
    arrivals = np.cumsum(rng.exponential(mean_interarrival, n_requests))
    trace = []
    for i in range(n_requests):
        t = int(rng.integers(0, n_tenants))
        if rng.random() < shared_frac:
            suffix = rng.integers(
                0, model.vocab_size, int(rng.integers(4, 17))
            ).astype(np.int32)
            prompt = np.concatenate([prefixes[t], suffix])
        else:
            prompt = rng.integers(
                0, model.vocab_size, int(rng.integers(16, 33))
            ).astype(np.int32)
        trace.append(ScheduledRequest(
            arrival_s=float(arrivals[i]), tenant=f"tenant{t}",
            prompt=prompt,
            max_new_tokens=int(rng.choice([6, 24], p=[0.6, 0.4])),
            # A quarter of the stream is multi-turn: sessions ride the
            # recorded trace and exercise sticky decode placement.
            session=f"sess{t}-{i % 4}" if rng.random() < 0.25 else None,
        ))
    # The recorded-trace round trip: both topologies replay these bytes.
    schedule = schedule_from_trace(schedule_to_records(trace))
    useful_tokens = sum(s.max_new_tokens for s in schedule)
    policy = SloPolicy(ttft_ms=ttft_ms, tpot_ms=tpot_ms, target=0.9)
    n_replicas = n_prefill + n_decode
    compile_watch.install()

    def run_topology(mode):
        roles = (
            ["prefill"] * n_prefill + ["decode"] * n_decode
            if mode == "disagg" else ["both"] * n_replicas
        )
        router = Router.build(
            model, variables, roles=roles, max_batch=max_batch,
            kv_page_size=page_size, max_queue=2 * n_requests,
            # Oversized pools: prefix-cache residency never evicts at
            # steady state, so every pass sees the same hit lengths —
            # the same continuation buckets — and the zero-recompile
            # pin measures scheduling, not cache-churn noise.
            kv_pages=pool_factor * max_batch * (256 // page_size) + 1,
            router_kwargs={"slo": policy,
                           "slo_timelines": 4 * n_requests},
        )
        with router:
            host, port = router.serve_http(port=0)
            url = f"http://{host}:{port}"
            # Two untimed passes: compiles (prefill buckets, decode,
            # kv export/import) + prefix caches to steady state.
            for _ in range(2):
                run_open_loop(schedule, url=url, time_scale=0.0)
            timed_t0 = time.monotonic()
            err = None
            try:
                with compile_watch.expect_no_compiles(f"disagg {mode}"):
                    client = run_open_loop(
                        schedule, url=url, collect_tokens=True
                    )
            except AssertionError as e:
                err = str(e)
                client = run_open_loop(
                    schedule, url=url, collect_tokens=True
                )
            server_side = aggregate_timelines(
                router.slo.timelines(since=timed_t0), policy
            )
            snap = router.snapshot()
        outputs = [r.get("output") for r in client["per_request"]]
        row = {
            "mode": mode,
            "replicas": len(roles),
            "tokens_per_sec": client["tokens_per_sec"],
            "makespan_s": client["makespan_s"],
            "n_errors": client["n_errors"],
            "ttft_p50_ms": server_side["ttft_ms"]["p50"],
            "ttft_p99_ms": server_side["ttft_ms"]["p99"],
            "tpot_p99_ms": server_side["tpot_ms"]["p99"],
            "e2e_p99_ms": server_side["e2e_ms"]["p99"],
            "attainment": server_side["attainment"],
            "n_timelines": server_side["n_requests"],
            "migrations": snap["migrations_total"],
            "kv_migrated_bytes": snap["kv_migrated_bytes_total"],
            "redistributes": snap["redistributes_total"],
            "zero_recompiles": err is None,
        }
        if err is not None:
            row["recompile_error"] = err
        print(
            f"# serve disagg [{mode:>9}]: {row['tokens_per_sec']:,.1f} "
            f"tokens/s, TTFT p50 {row['ttft_p50_ms']} ms / p99 "
            f"{row['ttft_p99_ms']} ms, {row['migrations']} migration(s)"
            + ("" if err is None else "  [RECOMPILED]"),
            flush=True,
        )
        return row, outputs

    disagg, disagg_outs = run_topology("disagg")
    coloc, coloc_outs = run_topology("colocated")
    identical = (
        all(o is not None for o in disagg_outs + coloc_outs)
        and all(a == b for a, b in zip(disagg_outs, coloc_outs))
    )
    ratio = (
        round(disagg["ttft_p99_ms"] / coloc["ttft_p99_ms"], 3)
        if coloc["ttft_p99_ms"] else None
    )
    result = {
        "disagg": disagg,
        "colocated": coloc,
        "ttft_p99_ratio": ratio,
        "ttft_win": bool(ratio is not None and ratio < 1.0),
        "byte_identical": identical,
        "zero_recompiles": bool(
            disagg["zero_recompiles"] and coloc["zero_recompiles"]
        ),
        "n_requests": n_requests,
        "n_tenants": n_tenants,
        "shared_frac": shared_frac,
        "shared_len": shared_len,
        "page_size": page_size,
        "max_batch": max_batch,
        "n_prefill": n_prefill,
        "n_decode": n_decode,
        "useful_tokens": useful_tokens,
        "seed": seed,
        "backend": jax.default_backend(),
    }
    if not identical:
        result["error"] = "disaggregated output diverged from colocated"
    elif not result["zero_recompiles"]:
        result["error"] = "compiles observed during a timed pass"
    elif disagg["n_errors"] or coloc["n_errors"]:
        result["error"] = (
            f"client errors: disagg {disagg['n_errors']}, colocated "
            f"{coloc['n_errors']}"
        )
    elif not result["ttft_win"]:
        result["error"] = (
            f"disaggregated p99 TTFT did not beat colocated "
            f"(ratio {ratio})"
        )
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# serve disagg artifact -> {out_path}", flush=True)
    return result


def bench_serve_fleet(n_requests=32, n_tenants=2, long_frac=0.4,
                      mean_interarrival=0.05, long_len=176,
                      short_hi=24, page_size=16, max_batch=4,
                      prefill_chunk=64, pool_factor=3, seed=0,
                      ttft_ms=1000.0, tpot_ms=1000.0, out_path=None):
    """True multi-process serving fleet (serving/fleet.py,
    docs/serving.md "Multi-process fleet"): every replica its own OS
    process, the router driving them ONLY over HTTP sockets, KV
    migration as real serialized bytes CRC-verified at the receiving
    process.  Four legs, one committed artifact:

    * **fleet** — a 4-process fleet (2 prefill + 2 decode, chunked
      prefill at ``prefill_chunk``) replays a seeded long+short mix
      open-loop through the router front end: every output
      byte-identical to in-driver ``generate()``, zero post-warmup
      compiles PER REPLICA PROCESS (each worker's ``compile_watch``
      count via ``/v1/spec`` before/after the timed pass), migrations
      metered in socket bytes.
    * **short_only** — the same fleet replaying an all-short trace:
      context for how much of the mix's latency is the long prompts
      themselves (``mix_vs_short_tokens_ratio``).
    * **unchunked** — a second fleet with ``prefill_chunk=0`` replaying
      the SAME mix — the controlled comparison (identical workload,
      identical processes, only the chunking knob differs): long
      prompts head-of-line-block short requests' TTFT inside
      monolithic prefills; the ``chunked_ttft_ratio`` (chunked /
      unchunked short-request p99 TTFT, win <= 1.0) pins the
      HOL-blocking win, and ``chunked_tokens_ratio`` (chunked /
      unchunked mix tokens/s, floor 0.9) pins that the per-window
      dispatch overhead does not tax throughput.  Arrivals come in
      longs-first bursts at a non-saturating rate, so every short
      request contends with an in-flight long prefill by construction
      — under saturated Poisson arrivals TTFT measures queue drain,
      and at low rates a short only collides with a ~10 ms monolithic
      prefill by luck.
    * **chaos** — a REAL ``SIGKILL`` of a decode worker mid-stream:
      every in-flight stream redistributes byte-identical, and the
      SLO-burn autoscaler respawns a real replacement process.

    Method guards as in ``bench_serve_disagg``: the traces are fixed
    (seeded + recorded-trace round trip) before any run; each fleet
    replays each trace twice untimed (workers compile to steady state
    against the shared on-disk cache) before its timed pass.  Workers
    run with the prefix cache OFF and the router with hedging OFF —
    replayed traces must genuinely re-prefill (else the timed pass is
    all prefix hits and chunking never engages) and placement must be
    deterministic across passes (hedge duplicates compile fresh
    buckets on whichever replica straggles that run)."""
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import Autoscaler, AutoscalerConfig
    from ml_trainer_tpu.serving.fleet import Fleet
    from ml_trainer_tpu.serving import SloPolicy
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )
    from ml_trainer_tpu.serving.slo import aggregate_timelines
    from ml_trainer_tpu.generate import generate

    max_len = 256
    model = get_model("gpt2_tiny", max_len=max_len)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    rng = np.random.default_rng(seed)

    def make_trace(frac_long):
        # Burst arrivals, longs first within each burst: every short
        # request lands WHILE a long prefill is in flight on its
        # prefill replica, so the TTFT comparison below measures
        # head-of-line blocking by construction (Poisson arrivals at a
        # rate low enough to avoid queue-drain TTFT only collide a
        # short with a ~10 ms monolithic prefill by luck).
        burst = 4
        n_long = int(round(burst * frac_long)) if frac_long else 0
        rows = []
        for i in range(n_requests):
            b, j = divmod(i, burst)
            is_long = j < n_long
            if is_long:
                n = int(rng.integers(long_len - 16, long_len + 17))
            else:
                n = int(rng.integers(8, short_hi + 1))
            rows.append(ScheduledRequest(
                arrival_s=float(
                    b * burst * mean_interarrival + j * 1e-4
                ),
                tenant=f"tenant{i % n_tenants}",
                prompt=rng.integers(
                    0, model.vocab_size, n
                ).astype(np.int32),
                max_new_tokens=int(rng.choice([8, 20], p=[0.4, 0.6])),
            ))
        return schedule_from_trace(schedule_to_records(rows))

    trace_mix = make_trace(long_frac)
    trace_short = make_trace(0.0)
    refs = {
        id(tr): [
            [int(t) for t in np.asarray(
                generate(model, variables, s.prompt[None],
                         s.max_new_tokens)
            )[0]]
            for s in tr
        ]
        for tr in (trace_mix, trace_short)
    }
    policy = SloPolicy(ttft_ms=ttft_ms, tpot_ms=tpot_ms, target=0.9)
    kv_pages = pool_factor * max_batch * (max_len // page_size) + 1

    def worker_compiles(fleet):
        out = {}
        for name, rep in fleet.replicas.items():
            try:
                out[name] = int(rep._get("/v1/spec")["compiles"] or 0)
            except Exception:
                out[name] = None
        return out

    def timed_pass(fleet, router, url, trace, mode, short_max=None):
        before = worker_compiles(fleet)
        chunks_before = 0
        for rep in fleet.replicas.values():
            try:
                chunks_before += int(rep._get("/metrics.json").get(
                    "prefill_chunks_total", 0
                ))
            except Exception:
                pass
        timed_t0 = time.monotonic()
        client = run_open_loop(trace, url=url, collect_tokens=True)
        after = worker_compiles(fleet)
        tls = router.slo.timelines(since=timed_t0)
        agg = aggregate_timelines(tls, policy)
        short_agg = None
        if short_max is not None:
            short_tls = [
                tl for tl in tls
                if tl.get("prompt_tokens") is not None
                and tl["prompt_tokens"] <= short_max
            ]
            short_agg = aggregate_timelines(short_tls, policy)
        chunks_after = 0
        for rep in fleet.replicas.values():
            try:
                chunks_after += int(rep._get("/metrics.json").get(
                    "prefill_chunks_total", 0
                ))
            except Exception:
                pass
        identical = all(
            r.get("output") == ref
            for r, ref in zip(client["per_request"], refs[id(trace)])
        )
        fresh = {
            n: (after[n] - before[n])
            if before.get(n) is not None and after.get(n) is not None
            else None
            for n in after
        }
        snap = router.snapshot()
        row = {
            "mode": mode,
            "tokens_per_sec": client["tokens_per_sec"],
            "makespan_s": client["makespan_s"],
            "n_errors": client["n_errors"],
            "ttft_p50_ms": agg["ttft_ms"]["p50"],
            "ttft_p99_ms": agg["ttft_ms"]["p99"],
            "byte_identical": identical,
            "migrations": snap["migrations_total"],
            "kv_migrated_bytes": snap["kv_migrated_bytes_total"],
            "prefill_chunks": chunks_after - chunks_before,
            "worker_compiles_timed": fresh,
            "zero_recompiles": all(v == 0 for v in fresh.values()),
        }
        if short_agg is not None:
            row["short_ttft_p50_ms"] = short_agg["ttft_ms"]["p50"]
            row["short_ttft_p99_ms"] = short_agg["ttft_ms"]["p99"]
            row["short_n"] = short_agg["n_requests"]
        print(
            f"# serve fleet [{mode:>10}]: {row['tokens_per_sec']:,.1f} "
            f"tokens/s, TTFT p99 {row['ttft_p99_ms']} ms"
            + (f" (short p99 {row.get('short_ttft_p99_ms')} ms)"
               if short_agg is not None else "")
            + f", {row['prefill_chunks']} chunk(s)"
            + ("" if row["zero_recompiles"] else "  [RECOMPILED]"),
            flush=True,
        )
        return row

    def run_fleet(chunk, legs):
        fleet = Fleet(
            roles=["prefill", "prefill", "decode", "decode"],
            model_name="gpt2_tiny", max_len=max_len,
            max_batch=max_batch, max_queue=2 * n_requests,
            kv_page_size=page_size, kv_pages=kv_pages, seed=0,
            prefill_chunk=chunk,
            # The prefix cache would turn the replayed traces into full
            # prefix hits after warmup, so the timed pass would never
            # exercise chunked prefill (and the chunked-vs-monolithic
            # TTFT comparison would measure cache lookups, not
            # prefills).  Hedging is off for the same reason: hedge
            # duplicates land on whichever replica is slow THAT run,
            # compiling fresh buckets mid-timed-pass.
            prefix_cache=False,
        )
        fleet.start()
        router = fleet.make_router(
            slo=policy, slo_timelines=4 * n_requests, hedging=False,
        )
        rows = {}
        chaos = None
        try:
            host, port = router.serve_http(port=0)
            url = f"http://{host}:{port}"
            warmed = set()
            for tr, _, _ in legs:
                if id(tr) in warmed:
                    continue
                warmed.add(id(tr))
                for _ in range(2):  # untimed: workers compile
                    run_open_loop(tr, url=url, time_scale=0.0)
            for tr, mode, short_max in legs:
                rows[mode] = timed_pass(
                    fleet, router, url, tr, mode, short_max=short_max
                )
            if chunk:  # chaos leg rides the chunked fleet
                chaos = chaos_leg(fleet, router)
        finally:
            router.close()
            fleet.stop()
        return rows, chaos

    def chaos_leg(fleet, router):
        subset = [s for s in trace_mix[:8]]
        c_refs = [
            [int(t) for t in np.asarray(
                generate(model, variables, s.prompt[None],
                         s.max_new_tokens)
            )[0]]
            for s in subset
        ]
        streams = [
            router.submit(s.prompt, s.max_new_tokens) for s in subset
        ]
        deadline = time.monotonic() + 120
        while any(len(s.tokens) < 2 for s in streams):
            if time.monotonic() > deadline:
                return {"error": "chaos streams never started decoding"}
            time.sleep(0.02)
        victim = fleet.replicas["decode0"]
        kill_t0 = time.monotonic()
        fleet.kill("decode0")
        autoscaler = Autoscaler(
            router, fleet.factory,
            AutoscalerConfig(poll_interval_s=0.2, min_prefill=2,
                             min_decode=2, replace_cooldown_s=0.2),
        ).start()
        try:
            outs = [
                [int(t) for t in np.asarray(s.result(timeout=300))]
                for s in streams
            ]
            identical = outs == c_refs
            respawn_s = None
            new_pid = None
            while time.monotonic() < deadline + 180:
                fresh = [
                    r for r in router.replicas.values()
                    if r.healthy and not r.removing
                    and r.name.startswith("auto")
                ]
                if fresh:
                    respawn_s = round(time.monotonic() - kill_t0, 3)
                    new_pid = fresh[0].server.pid
                    break
                time.sleep(0.1)
        finally:
            autoscaler.close()
        snap = router.snapshot()
        return {
            "killed_pid": victim.pid,
            "respawned_pid": new_pid,
            "respawn_s": respawn_s,
            "redistributes": snap["redistributes_total"],
            "byte_identical": identical,
        }

    chunked_rows, chaos = run_fleet(prefill_chunk, [
        (trace_mix, "fleet", short_hi),
        (trace_short, "short_only", None),
    ])
    unchunked_rows, _ = run_fleet(0, [
        (trace_mix, "unchunked", short_hi),
    ])
    fleet_row = chunked_rows["fleet"]
    short_row = chunked_rows["short_only"]
    unchunked = unchunked_rows["unchunked"]
    ttft_ratio = (
        round(fleet_row["short_ttft_p99_ms"]
              / unchunked["short_ttft_p99_ms"], 3)
        if unchunked.get("short_ttft_p99_ms") else None
    )
    tokens_ratio = (
        round(fleet_row["tokens_per_sec"]
              / unchunked["tokens_per_sec"], 3)
        if unchunked["tokens_per_sec"] else None
    )
    mix_vs_short = (
        round(fleet_row["tokens_per_sec"]
              / short_row["tokens_per_sec"], 3)
        if short_row["tokens_per_sec"] else None
    )
    rows = [fleet_row, short_row, unchunked]
    result = {
        "fleet": fleet_row,
        "short_only": short_row,
        "unchunked": unchunked,
        "chaos": chaos,
        "chunked_ttft_ratio": ttft_ratio,
        "chunked_tokens_ratio": tokens_ratio,
        "mix_vs_short_tokens_ratio": mix_vs_short,
        "ttft_win": bool(ttft_ratio is not None and ttft_ratio <= 1.0),
        "tokens_floor": bool(
            tokens_ratio is not None and tokens_ratio >= 0.9
        ),
        "byte_identical": bool(
            all(r["byte_identical"] for r in rows)
            and chaos is not None and chaos.get("byte_identical")
        ),
        "zero_recompiles": all(r["zero_recompiles"] for r in rows),
        "n_requests": n_requests,
        "long_frac": long_frac,
        "long_len": long_len,
        "page_size": page_size,
        "max_batch": max_batch,
        "prefill_chunk": prefill_chunk,
        "seed": seed,
        "backend": jax.default_backend(),
    }
    if not result["byte_identical"]:
        result["error"] = "fleet output diverged from generate()"
    elif not result["zero_recompiles"]:
        result["error"] = "worker compiles observed during a timed pass"
    elif any(r["n_errors"] for r in rows):
        result["error"] = (
            f"client errors: {[r['n_errors'] for r in rows]}"
        )
    elif fleet_row["prefill_chunks"] < 1:
        result["error"] = "chunked prefill never engaged on the mix"
    elif chaos is None or chaos.get("respawned_pid") is None:
        result["error"] = "autoscaler never respawned the killed worker"
    elif not result["ttft_win"]:
        result["error"] = (
            f"chunked prefill did not hold short-request p99 TTFT "
            f"(ratio {ttft_ratio})"
        )
    elif not result["tokens_floor"]:
        result["error"] = (
            f"chunked prefill taxed mix tokens/s below 0.9x the "
            f"unchunked fleet (ratio {tokens_ratio})"
        )
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# serve fleet artifact -> {out_path}", flush=True)
    return result


def bench_fleet_obs(n_requests=12, n_tenants=2, mean_interarrival=0.02,
                    page_size=16, max_batch=2, pool_factor=3, seed=0,
                    scrape_iters=20, out_path=None):
    """Fleet observability plane (serving/router.py "fleet plane",
    docs/observability.md "Fleet plane") measured on a REAL 3-process
    fleet: the cost of watching the fleet, plus the invariants that
    make the watching trustworthy.  One committed artifact
    (docs/fleet_obs_cpu.json):

    * **overhead** — wall-clock for one federated ``/metrics`` scrape
      sweep (router pulls every worker's exposition over HTTP), one
      federated render (relabel + merge into the router's own
      exposition), one fleet trace merge (``GET /trace`` from every
      worker, clock-align, merge into a single Perfetto timeline), and
      one full incident-bundle assembly.  All host-side, all off the
      request path — the numbers bound what the plane costs the router
      thread, not the workers.
    * **federation invariants** — every worker series appears in the
      federated exposition carrying ``replica=``/``role=``/
      ``generation=`` labels, including each worker's
      ``compile_events_post_warmup_total`` (rendered at 0, so absence
      means "watch missing", never "no recompile yet"); a re-scrape +
      re-render is byte-identical on the worker sections (snapshots
      replace — histograms cannot double-count).
    * **trace invariants** — the merged timeline holds >= 2 process
      lanes and a migrated request whose prefill-side fragment (on the
      prefill worker's lane) ends before its decode-side span (on a
      DIFFERENT pid's lane) begins, after clock alignment.
    * **plane-is-free invariants** — with the plane fully enabled
      (scraping, tracing, bundling), the replayed trace stays
      byte-identical to in-driver ``generate()`` and every worker
      reports zero post-warmup compiles; loadgen rows carry the
      serving replica id.
    """
    import os
    import tempfile

    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving.fleet import Fleet
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )
    from ml_trainer_tpu.generate import generate

    max_len = 128
    model = get_model("gpt2_tiny", max_len=max_len)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    rng = np.random.default_rng(seed)
    rows = [
        ScheduledRequest(
            arrival_s=i * mean_interarrival,
            tenant=f"tenant{i % n_tenants}",
            prompt=rng.integers(
                0, model.vocab_size, int(rng.integers(8, 25))
            ).astype(np.int32),
            max_new_tokens=int(rng.choice([6, 10])),
        )
        for i in range(n_requests)
    ]
    trace = schedule_from_trace(schedule_to_records(rows))
    refs = [
        [int(t) for t in np.asarray(
            generate(model, variables, s.prompt[None], s.max_new_tokens)
        )[0]]
        for s in trace
    ]
    kv_pages = pool_factor * max_batch * (max_len // page_size) + 1

    def worker_compiles(fleet):
        out = {}
        for name, rep in fleet.replicas.items():
            try:
                out[name] = int(rep._get("/v1/spec")["compiles"] or 0)
            except Exception:
                out[name] = None
        return out

    def _ms(samples):
        if not samples:
            return None
        s = sorted(samples)
        return {
            "mean_ms": round(sum(s) / len(s) * 1e3, 3),
            "p50_ms": round(s[len(s) // 2] * 1e3, 3),
            "max_ms": round(s[-1] * 1e3, 3),
            "n": len(s),
        }

    def worker_lines(text):
        # The federated exposition's worker sections: every sample line
        # that carries a replica= label (router-own series do not).
        return [
            ln for ln in text.splitlines()
            if ln and not ln.startswith("#") and 'replica="' in ln
        ]

    fleet = Fleet(
        roles=["prefill", "decode", "decode"], model_name="gpt2_tiny",
        max_len=max_len, max_batch=max_batch, max_queue=4 * n_requests,
        kv_page_size=page_size, kv_pages=kv_pages, seed=0,
        prefix_cache=False,
    )
    fleet.start()
    incident_root = tempfile.mkdtemp(prefix="fleet-obs-incident-")
    router = fleet.make_router(
        hedging=False, metrics_scrape_interval=0.1,
        incident_dir=incident_root, incident_min_interval_s=0.0,
    )
    result = {
        "n_requests": n_requests,
        "page_size": page_size,
        "max_batch": max_batch,
        "seed": seed,
        "backend": jax.default_backend(),
    }
    try:
        host, port = router.serve_http(port=0)
        url = f"http://{host}:{port}"
        for _ in range(2):  # untimed: workers compile to steady state
            run_open_loop(trace, url=url, time_scale=0.0)
        before = worker_compiles(fleet)
        client = run_open_loop(trace, url=url, collect_tokens=True)
        after = worker_compiles(fleet)
        fresh = {
            n: (after[n] - before[n])
            if before.get(n) is not None and after.get(n) is not None
            else None
            for n in after
        }
        identical = all(
            r.get("output") == ref
            for r, ref in zip(client["per_request"], refs)
        )
        rows_with_replica = sum(
            1 for r in client["per_request"] if r.get("replica")
        )

        # Overhead: scrape sweep / federated render / trace merge.
        scrape_s, render_s = [], []
        for _ in range(scrape_iters):
            t0 = time.perf_counter()
            router.scrape_metrics(force=True)
            scrape_s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            text = router.federated_metrics_text()
            render_s.append(time.perf_counter() - t0)
        lines_a = worker_lines(text)
        router.scrape_metrics(force=True)
        lines_b = worker_lines(router.federated_metrics_text())
        idempotent = lines_a == lines_b
        workers = sorted(fleet.replicas)
        fed_ok = all(
            any(
                ln.startswith("compile_events_post_warmup_total{")
                and f'replica="{name}"' in ln and 'role="' in ln
                and 'generation="' in ln
                for ln in lines_a
            )
            for name in workers
        )

        t0 = time.perf_counter()
        merged = router.fleet_trace()
        merge_s = time.perf_counter() - t0
        events = merged.get("traceEvents", [])
        lanes = {
            e.get("pid") for e in events if e.get("ph") != "M"
        }
        # A migrated request: its kv_wire span names the trace id; the
        # prefill fragment and decode span must sit on different lanes
        # in causal order after clock alignment.
        causal = None
        router_pid = os.getpid()  # the router's lane: its own request
        for ev in events:         # spans start at submit, pre-prefill
            name = ev.get("name", "")
            if not name.startswith("kv_wire "):
                continue
            tid = name.split(" ", 1)[1]
            pre = next(
                (e for e in events
                 if e.get("name") == f"request {tid} (prefill)"), None,
            )
            dec = next(
                (e for e in events
                 if e.get("name") == f"request {tid}"
                 and e.get("pid") not in (
                     (pre or {}).get("pid"), router_pid,
                 )), None,
            )
            if pre is None or dec is None:
                continue
            pre_end = pre["ts"] + pre.get("dur", 0.0)
            causal = {
                "trace_id": tid,
                "prefill_pid": pre["pid"],
                "decode_pid": dec["pid"],
                "gap_us": round(dec["ts"] - pre_end, 1),
                # Epoch alignment is exact on one host; allow the NTP
                # fallback's rtt/2 error bound.
                "ordered": bool(dec["ts"] >= pre_end - 5_000.0),
            }
            if causal["ordered"]:
                break

        t0 = time.perf_counter()
        bundle = router.save_incident_bundle(
            "bench_fleet_obs", force=True,
        )
        bundle_s = time.perf_counter() - t0
        bundle_files = sorted(os.listdir(bundle)) if bundle else []
        want = {"flight_router.json", "metrics.prom", "manifest.json",
                "slo_timelines.json", "router.json"}
        want |= {f"flight_{n}.json" for n in workers}
        bundle_ok = bundle is not None and want <= set(bundle_files)

        result.update({
            "scrape": _ms(scrape_s),
            "federated_render": _ms(render_s),
            "trace_merge_ms": round(merge_s * 1e3, 3),
            "bundle_assembly_ms": round(bundle_s * 1e3, 3),
            "federated_lines": len(lines_a),
            "federated_labels_ok": bool(fed_ok),
            "idempotent_rescrape": bool(idempotent),
            "trace_lanes": len(lanes),
            "trace_events": len(events),
            "migrated_request": causal,
            "fleet_clock": {
                n: {"method": c.get("method"),
                    "rtt_us": c.get("rtt_us")}
                for n, c in merged.get("fleetClock", {}).items()
            },
            "bundle_files": bundle_files,
            "bundle_ok": bool(bundle_ok),
            "rows_with_replica": rows_with_replica,
            "n_errors": client["n_errors"],
            "byte_identical": bool(identical),
            "worker_compiles_timed": fresh,
            "zero_recompiles": all(v == 0 for v in fresh.values()),
        })
    finally:
        router.close()
        fleet.stop()
    if not result.get("byte_identical"):
        result["error"] = (
            "fleet output diverged from generate() with the plane on"
        )
    elif not result.get("zero_recompiles"):
        result["error"] = "worker compiles observed during a timed pass"
    elif result.get("n_errors"):
        result["error"] = f"client errors: {result['n_errors']}"
    elif not result.get("federated_labels_ok"):
        result["error"] = (
            "federated exposition missing worker series/labels"
        )
    elif not result.get("idempotent_rescrape"):
        result["error"] = "re-scrape changed the federated worker lines"
    elif result.get("trace_lanes", 0) < 2:
        result["error"] = (
            f"merged trace holds {result.get('trace_lanes')} lane(s)"
        )
    elif not (result.get("migrated_request") or {}).get("ordered"):
        result["error"] = (
            "no migrated request in causal order across two lanes"
        )
    elif not result.get("bundle_ok"):
        result["error"] = (
            f"incident bundle incomplete: {result.get('bundle_files')}"
        )
    elif result.get("rows_with_replica", 0) < n_requests:
        result["error"] = (
            f"only {result.get('rows_with_replica')}/{n_requests} "
            "loadgen rows carried a serving replica id"
        )
    print(
        "# fleet obs: scrape "
        f"{(result.get('scrape') or {}).get('mean_ms')} ms, render "
        f"{(result.get('federated_render') or {}).get('mean_ms')} ms, "
        f"merge {result.get('trace_merge_ms')} ms "
        f"({result.get('trace_lanes')} lanes), bundle "
        f"{result.get('bundle_assembly_ms')} ms"
        + ("" if not result.get("error") else
           f"  [FAILED: {result['error']}]"),
        flush=True,
    )
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# fleet obs artifact -> {out_path}", flush=True)
    return result


def bench_watchtower(sample_iters=200, eval_iters=200, render_iters=20,
                     n_hosts=3, out_path=None):
    """Watchtower overhead (telemetry/watchtower.py + alerts.py,
    docs/observability.md "Watchtower"): what the TSDB + alert engine
    + dashboard cost the host thread that already runs the publish
    loops, plus the invariants that make the watching trustworthy.
    One committed artifact (docs/watchtower_cpu.json):

    * **overhead** — per-call wall-clock for one full registry sample
      into the ring store (a serving-worker-sized registry: gauges,
      counters, labeled histograms), one exposition ingest (the
      router's federation path), one declarative alert-engine tick
      (threshold + rate + burn + quantile + absent rules over every
      label group), one windowed quantile query, and one dashboard
      render.  All host-side, zero device work.
    * **detection invariant** — an injected latency regression (the
      TTFT histogram's observations jump 10x) must trip the
      ``quantile_over_time`` rule on the FIRST evaluation after the
      regressed samples land: detection latency is one sample tick +
      one eval tick, never a window.
    * **storage invariants** — rings stay bounded at their capacity
      under sustained sampling, and a ``dump()`` -> ``load()``
      round-trip is exact.

    The ratcheted headline is ``sample_ops_per_sec`` (how many full
    registry sweeps one core sustains) — the number that bounds what
    the TSDB costs every publish cadence in the process.
    """
    from ml_trainer_tpu.telemetry.alerts import AlertEngine, AlertRule
    from ml_trainer_tpu.telemetry.export import prometheus_text
    from ml_trainer_tpu.telemetry.flight import FlightRecorder
    from ml_trainer_tpu.telemetry.registry import MetricsRegistry
    from ml_trainer_tpu.telemetry.watchtower import (
        TimeSeriesStore, render_dashboard,
    )

    def _ms(samples):
        if not samples:
            return None
        s = sorted(samples)
        return {
            "mean_ms": round(sum(s) / len(s) * 1e3, 3),
            "p50_ms": round(s[len(s) // 2] * 1e3, 3),
            "max_ms": round(s[-1] * 1e3, 3),
            "n": len(s),
        }

    # A serving-worker-sized registry: the per-tenant latency
    # histograms plus a spread of gauges/counters with host labels.
    registry = MetricsRegistry()
    rng = np.random.default_rng(0)
    hists = [
        registry.histogram(
            f"serving_{which}_seconds", f"{which} latency",
            labelnames=("tenant",),
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
        )
        for which in ("ttft", "tpot", "queue_wait", "e2e")
    ]
    for h in hists:
        for tenant in ("alpha", "beta", "gamma"):
            for v in rng.uniform(0.002, 0.04, 64):
                h.labels(tenant=tenant).observe(float(v))
    gauges = [
        registry.gauge(f"watch_gauge_{i}", f"gauge {i}",
                       labelnames=("host",))
        for i in range(24)
    ]
    counters = [
        registry.counter(f"watch_counter_{i}", f"counter {i}",
                         labelnames=("host",))
        for i in range(12)
    ]
    for h in range(n_hosts):
        for g in gauges:
            g.labels(host=str(h)).set(float(rng.uniform(0, 100)))
        for c in counters:
            c.labels(host=str(h)).inc(int(rng.integers(1, 50)))

    result = {
        "backend": jax.default_backend(),
        "n_hosts": n_hosts,
        "sample_iters": sample_iters,
    }

    # -- sampling overhead (the trainer/server publish-cadence cost) --
    store = TimeSeriesStore(capacity=256)
    sample_s = []
    t = 0.0
    for _ in range(sample_iters):
        t += 1.0
        t0 = time.perf_counter()
        store.sample_registry(registry, t=t, force=True)
        sample_s.append(time.perf_counter() - t0)
    result["sample"] = _ms(sample_s)
    result["series"] = len(store)
    result["sample_ops_per_sec"] = round(
        1.0 / max(sum(sample_s) / len(sample_s), 1e-9), 1
    )

    # -- ingest overhead (the router federation path) --
    text = prometheus_text(registry)
    ingest_store = TimeSeriesStore(capacity=256)
    ingest_s = []
    for i in range(max(sample_iters // 4, 1)):
        t0 = time.perf_counter()
        ingest_store.ingest_exposition(
            text, t=float(i),
            extra_labels={"replica": "w0", "role": "decode",
                          "generation": "0"},
            force=True,
        )
        ingest_s.append(time.perf_counter() - t0)
    result["ingest"] = _ms(ingest_s)
    result["exposition_bytes"] = len(text)

    # -- alert-engine tick + windowed-query overhead --
    flight = FlightRecorder()
    engine = AlertEngine(
        rules=[
            AlertRule("gauge_high", "watch_gauge_0 > 1e9"),
            AlertRule("counter_rate",
                      "rate(watch_counter_0[32s]) > 1e9"),
            AlertRule("burn_avg", "avg(watch_gauge_1[32s]) > 1e9",
                      for_s=5.0),
            AlertRule("ttft_q50",
                      "quantile(0.5, serving_ttft_seconds{"
                      'tenant=alpha}[32s]) > 0.2', for_count=1),
            AlertRule("absent_series", "absent(no_such_series[32s])",
                      severity="info"),
        ],
        store=store, registry=registry, flight=flight,
    )
    eval_s = []
    for i in range(eval_iters):
        t0 = time.perf_counter()
        engine.evaluate(now=t)
        eval_s.append(time.perf_counter() - t0)
    result["alert_eval"] = _ms(eval_s)
    query_s = []
    for _ in range(eval_iters):
        t0 = time.perf_counter()
        store.quantile_over_time(
            "serving_ttft_seconds", 0.5, labels={"tenant": "alpha"},
            window_s=32.0, now=t,
        )
        query_s.append(time.perf_counter() - t0)
    result["quantile_query"] = _ms(query_s)

    # -- dashboard render --
    render_s = []
    html = ""
    for _ in range(render_iters):
        t0 = time.perf_counter()
        html = render_dashboard(store, title="bench")
        render_s.append(time.perf_counter() - t0)
    result["dashboard_render"] = _ms(render_s)
    result["dashboard_bytes"] = len(html)

    # -- detection invariant: a 10x TTFT regression trips the
    # quantile rule on the first eval after the regressed samples land.
    assert not engine.rule("ttft_q50").firing()
    for v in rng.uniform(0.3, 0.5, 48):  # the regression
        hists[0].labels(tenant="alpha").observe(float(v))
    t += 1.0
    store.sample_registry(registry, t=t, force=True)
    detect_t0 = time.perf_counter()
    events = engine.evaluate(now=t)
    detect_ms = (time.perf_counter() - detect_t0) * 1e3
    fired = [
        e for e in events
        if e["rule"] == "ttft_q50" and e["state"] == "firing"
    ]
    result["detection"] = {
        "fired_first_eval": bool(fired),
        "eval_ms": round(detect_ms, 3),
        "quantile_seen": fired[0]["value"] if fired else None,
        "flight_alerts": sum(
            1 for r in flight.records() if r.get("kind") == "alert"
        ),
    }

    # -- storage invariants --
    bounded = all(
        len(points) <= 256
        for _, points in store.select("serving_ttft_seconds_bucket", {})
    ) and len(store.last("watch_gauge_0", {"host": "0"}, n=10 ** 6)) <= 256
    dump = store.dump()
    roundtrip = TimeSeriesStore.load(dump).dump() == dump
    result["ring_bounded"] = bool(bounded)
    result["dump_roundtrip_exact"] = bool(roundtrip)

    if not result["detection"]["fired_first_eval"]:
        result["error"] = (
            "injected TTFT regression did not fire the quantile rule "
            "on the first evaluation"
        )
    elif not result["ring_bounded"]:
        result["error"] = "ring exceeded its capacity under sampling"
    elif not result["dump_roundtrip_exact"]:
        result["error"] = "dump -> load round-trip not exact"
    print(
        "# watchtower: sample "
        f"{(result.get('sample') or {}).get('mean_ms')} ms "
        f"({result['series']} series, "
        f"{result['sample_ops_per_sec']} sweeps/s), ingest "
        f"{(result.get('ingest') or {}).get('mean_ms')} ms, eval "
        f"{(result.get('alert_eval') or {}).get('mean_ms')} ms, render "
        f"{(result.get('dashboard_render') or {}).get('mean_ms')} ms"
        + ("" if not result.get("error") else
           f"  [FAILED: {result['error']}]"),
        flush=True,
    )
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# watchtower artifact -> {out_path}", flush=True)
    return result


def bench_serve_deploy(n_requests=24, n_tenants=8, mean_interarrival=0.12,
                       page_size=8, max_batch=4, seed=0,
                       ttft_ms=2000.0, tpot_ms=2000.0, wedge_s=3.0,
                       out_path=None):
    """Live base-model rollout on a multi-process fleet
    (serving/deploy.py, docs/serving.md "Deploys"): train a tiny gpt2
    in-bench, export it (manifest + weights fingerprint), then roll a
    live 2-process fleet onto the export UNDER OPEN-LOOP TRAFFIC.  Two
    legs, one committed artifact:

    * **deploy** — the fleet serves the seed init while a background
      client replays a seeded trace open-loop in a loop;
      ``Router.deploy(ckpt, canary=0.25)`` spawns new-generation
      worker PROCESSES loaded from the export (shared on-disk compile
      cache), warms them off-path, routes the tenant-hash canary slice
      at them, holds clean burn, ramps to 100% and retires the old
      workers — all while the client sees ZERO errors (no dropped
      streams) and every mid-deploy output is byte-identical to
      ``generate()`` on whichever weights its generation serves.  The
      old steady fleet's per-process compile counts (polled via
      ``/v1/spec`` until retirement) must not move during the deploy.
    * **rollback** — the SAME export deployed again (gen2 == gen1
      weights, so every output stays byte-checkable) through a wedged
      factory whose ``submit_request`` sleeps ``wedge_s`` — an honest
      TTFT regression on exactly the canary slice.  The burn watch
      trips, the deployment rolls back within one burn window, the
      fleet lands back on its pre-deploy replica set, and the client
      again sees zero errors and byte-identical outputs throughout.

    A final timed pass on the post-rollback fleet pins zero
    post-warmup recompiles + byte identity and is the throughput
    number ``gate_deploy`` ratchets."""
    import os
    import shutil
    import tempfile
    import threading

    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.checkpoint import (
        load_model_manifest, load_model_variables,
    )
    from ml_trainer_tpu.data import SyntheticTokens
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import DeployConfig, SloPolicy
    from ml_trainer_tpu.serving.fleet import Fleet
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )
    from ml_trainer_tpu.generate import generate

    max_len = 64
    model = get_model("gpt2_tiny", max_len=max_len)
    rng = np.random.default_rng(seed)
    work_dir = tempfile.mkdtemp(prefix="bench_deploy_")
    ckpt_dir = os.path.join(work_dir, "export")

    # The rollout target: a REAL export of a REAL (tiny) training run,
    # manifest + weights fingerprint included.
    ds = SyntheticTokens(size=32, seq_len=16,
                         vocab_size=model.vocab_size, seed=0)
    Trainer(model, datasets=(ds, ds), epochs=1, batch_size=8,
            metric=None, model_dir=ckpt_dir, seed=7, lr=0.01).fit()
    manifest = load_model_manifest(ckpt_dir) or {}
    trained = load_model_variables(ckpt_dir)
    # Workers spawned WITHOUT --ckpt init from PRNGKey(seed=0) — the
    # driver-side twin of the old generation's weights.
    seed_vars = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )

    policy = SloPolicy(ttft_ms=ttft_ms, tpot_ms=tpot_ms, target=0.9)
    kv_pages = 3 * max_batch * (max_len // page_size) + 1
    fleet = Fleet(
        roles=["both", "both"], model_name="gpt2_tiny", max_len=max_len,
        max_batch=max_batch, max_queue=2 * n_requests,
        kv_page_size=page_size, kv_pages=kv_pages, seed=0,
        # Prefix cache off so looped replays genuinely re-prefill and
        # stay byte-comparable; hedging off so placement (and thus
        # which generation serves a mid-deploy request) follows the
        # tenant-hash split deterministically.
        prefix_cache=False,
    )
    fleet.start()
    router = fleet.make_router(
        slo=policy, slo_timelines=8 * n_requests, hedging=False,
    )
    result = {}
    try:
        host, port = router.serve_http(port=0)
        url = f"http://{host}:{port}"

        # Tenants chosen so the 0.25 canary slice holds exactly 2 of
        # the 8 — a stable cohort with traffic on BOTH sides of the
        # split every pass.
        canary_pool = [t for t in (f"t{i}" for i in range(64))
                       if router.tenant_slice(t) < 0.25][:2]
        stable_pool = [t for t in (f"t{i}" for i in range(64))
                       if router.tenant_slice(t) >= 0.25][:n_tenants - 2]
        tenants = (canary_pool + stable_pool)

        rows = []
        for i in range(n_requests):
            n = int(rng.integers(8, 17))
            rows.append(ScheduledRequest(
                arrival_s=float(i * mean_interarrival),
                tenant=tenants[i % len(tenants)],
                prompt=rng.integers(
                    0, model.vocab_size, n
                ).astype(np.int32),
                max_new_tokens=8,
            ))
        trace = schedule_from_trace(schedule_to_records(rows))
        refs_seed = [
            [int(t) for t in np.asarray(
                generate(model, seed_vars, s.prompt[None],
                         s.max_new_tokens)
            )[0]]
            for s in trace
        ]
        refs_trained = [
            [int(t) for t in np.asarray(
                generate(model, trained, s.prompt[None],
                         s.max_new_tokens)
            )[0]]
            for s in trace
        ]

        def live_compiles():
            out = {}
            for rep in list(router.replicas.values()):
                try:
                    out[rep.name] = int(
                        rep.server._get("/v1/spec")["compiles"] or 0
                    )
                except Exception:
                    pass
            return out

        class _Poller:
            """Samples every live replica's compile count until
            stopped — old-generation workers are retired (processes
            gone) at promote, so their final counts must be caught
            in flight."""

            def __init__(self):
                self.last_seen = {}
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, daemon=True)

            def _run(self):
                while not self._stop.is_set():
                    self.last_seen.update(live_compiles())
                    self._stop.wait(0.2)

            def __enter__(self):
                self._thread.start()
                return self

            def __exit__(self, *exc):
                self._stop.set()
                self._thread.join(timeout=5.0)

        class _Load:
            """Open-loop client looping the trace until stopped."""

            def __init__(self):
                self.passes = []
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, daemon=True)

            def _run(self):
                while not self._stop.is_set():
                    self.passes.append(run_open_loop(
                        trace, url=url, collect_tokens=True))

            def __enter__(self):
                self._thread.start()
                return self

            def __exit__(self, *exc):
                self._stop.set()
                self._thread.join(timeout=600.0)

            def n_errors(self):
                return sum(p["n_errors"] for p in self.passes)

            def outputs_ok(self, allowed_refs):
                for p in self.passes:
                    for i, r in enumerate(p["per_request"]):
                        if not any(r.get("output") == refs[i]
                                   for refs in allowed_refs):
                            return False
                return bool(self.passes)

        for _ in range(2):  # untimed: workers compile to steady state
            run_open_loop(trace, url=url, time_scale=0.0)

        cfg = DeployConfig(
            canary=0.25, stages=(1.0,), hold_s=1.5,
            burn_threshold=2.0, high_polls=2, window_s=10.0,
            min_window_requests=2, stage_min_requests=2,
            poll_interval_s=0.3, drain_timeout_s=60.0,
        )

        def deploy_leg(mode, factory, allowed_refs):
            pre_replicas = sorted(router.replicas)
            base = live_compiles()
            t0 = time.monotonic()
            with _Poller() as poller, _Load() as load:
                dep = router.deploy(ckpt_dir, canary=cfg.canary,
                                    factory=factory, config=cfg)
                verdict = dep.wait(timeout=600.0)
                elapsed = round(time.monotonic() - t0, 3)
                dep.close()
            steady = {
                n: poller.last_seen[n] - base[n]
                for n in base if n in poller.last_seen
            }
            rep = dep.report()
            first_burn = next(
                (e["t"] for e in rep["events"]
                 if e["action"] == "burn_high"), None,
            )
            rolled_back_t = next(
                (e["t"] for e in rep["events"]
                 if e["action"] == "transition"
                 and e.get("to") == "rolled_back"), None,
            )
            rollback_s = (
                round(rolled_back_t - first_burn, 3)
                if first_burn is not None and rolled_back_t is not None
                else None
            )
            row = {
                "mode": mode,
                "state": verdict,
                "deploy_s": elapsed,
                "weights_fp": rep["weights_fp"],
                "old_weights_fp": rep["old_weights_fp"],
                "last_burn": rep["last_burn"],
                "rollback_cause": rep["rollback_cause"],
                "rollback_s": rollback_s,
                "n_client_passes": len(load.passes),
                "n_client_errors": load.n_errors(),
                "byte_identical": load.outputs_ok(allowed_refs),
                "steady_fleet_compiles": steady,
                "zero_steady_recompiles": all(
                    v == 0 for v in steady.values()),
                "replicas_before": pre_replicas,
                "replicas_after": sorted(router.replicas),
                "events": [
                    {k: e[k] for k in ("t", "action", "state")}
                    for e in rep["events"]
                ],
            }
            print(
                f"# serve deploy [{mode:>9}]: {verdict} in "
                f"{elapsed:.1f}s, {len(load.passes)} client pass(es), "
                f"{row['n_client_errors']} error(s)"
                + (f", rollback {rollback_s}s after first high burn"
                   if rollback_s is not None else "")
                + ("" if row["zero_steady_recompiles"]
                   else "  [RECOMPILED]"),
                flush=True,
            )
            return row

        # Leg 1: healthy rollout mid-load.  Any mid-deploy output may
        # come from either generation, so either reference is valid.
        deploy_row = deploy_leg(
            "deploy", fleet.deploy_factory(ckpt_dir),
            (refs_seed, refs_trained),
        )

        # Leg 2: the SAME export again (gen2 weights == the now-serving
        # gen1, so every output stays checkable against the trained
        # refs) through a wedged factory — an honest canary-only TTFT
        # regression the burn watch must catch.
        base_factory = fleet.deploy_factory(ckpt_dir)

        def wedged_factory(role):
            remote = base_factory(role)
            orig = remote.submit_request

            def slow_submit(req):
                time.sleep(wedge_s)
                return orig(req)

            remote.submit_request = slow_submit
            return remote

        rollback_row = deploy_leg(
            "rollback", wedged_factory, (refs_trained,),
        )

        # Final timed pass on the post-rollback fleet: the promoted
        # generation, steady, zero recompiles — the ratchet number.
        before = live_compiles()
        client = run_open_loop(trace, url=url, collect_tokens=True)
        after = live_compiles()
        fresh = {
            n: after[n] - before[n] for n in after if n in before
        }
        final_row = {
            "tokens_per_sec": client["tokens_per_sec"],
            "makespan_s": client["makespan_s"],
            "n_errors": client["n_errors"],
            "byte_identical": all(
                r.get("output") == ref
                for r, ref in zip(client["per_request"], refs_trained)
            ),
            "worker_compiles_timed": fresh,
            "zero_recompiles": all(v == 0 for v in fresh.values()),
        }
        print(
            f"# serve deploy [    final]: "
            f"{final_row['tokens_per_sec']:,.1f} tokens/s on the "
            f"post-rollback fleet"
            + ("" if final_row["zero_recompiles"] else "  [RECOMPILED]"),
            flush=True,
        )

        result = {
            "deploy": deploy_row,
            "rollback": rollback_row,
            "final": final_row,
            "manifest_fingerprint": manifest.get("weights_fingerprint"),
            "fingerprint_match": bool(
                manifest.get("weights_fingerprint")
                and deploy_row["weights_fp"]
                == manifest["weights_fingerprint"]
            ),
            "rollback_within_window_s": cfg.window_s,
            "n_requests": n_requests,
            "n_tenants": n_tenants,
            "wedge_s": wedge_s,
            "seed": seed,
            "backend": jax.default_backend(),
        }
        zero_errors = (
            deploy_row["n_client_errors"] == 0
            and rollback_row["n_client_errors"] == 0
            and final_row["n_errors"] == 0
        )
        if deploy_row["state"] != "done":
            result["error"] = (
                f"healthy deploy ended {deploy_row['state']}, not done"
            )
        elif rollback_row["state"] != "rolled_back":
            result["error"] = (
                f"forced regression ended {rollback_row['state']}, "
                "not rolled_back"
            )
        elif not zero_errors:
            result["error"] = "client errors (dropped streams) observed"
        elif not (deploy_row["byte_identical"]
                  and rollback_row["byte_identical"]
                  and final_row["byte_identical"]):
            result["error"] = "fleet output diverged from generate()"
        elif not (deploy_row["zero_steady_recompiles"]
                  and rollback_row["zero_steady_recompiles"]
                  and final_row["zero_recompiles"]):
            result["error"] = (
                "steady-fleet compiles observed during a deploy"
            )
        elif rollback_row["rollback_s"] is None or (
                rollback_row["rollback_s"] > cfg.window_s):
            result["error"] = (
                f"rollback took {rollback_row['rollback_s']}s — "
                f"outside the {cfg.window_s}s burn window"
            )
        elif rollback_row["replicas_after"] != (
                rollback_row["replicas_before"]):
            result["error"] = (
                "rollback did not restore the pre-deploy replica set"
            )
        elif not result["fingerprint_match"]:
            result["error"] = (
                "served weights fingerprint != export manifest"
            )
    finally:
        try:
            router.close()
        finally:
            fleet.stop()
            shutil.rmtree(work_dir, ignore_errors=True)
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# serve deploy artifact -> {out_path}", flush=True)
    return result


def bench_serve_chaos(n_requests=96, n_tenants=3, shared_frac=0.8,
                      mean_interarrival=0.04, shared_len=160,
                      page_size=16, max_batch=4, seed=0,
                      ttft_ms=400.0, tpot_ms=1000.0, slo_target=0.9,
                      pool_factor=3, slow_secs=15.0, out_path=None):
    """Serving chaos: the recorded 80%-shared-prefix trace, open-loop at
    saturating load through a 2-prefill + 2-decode router fleet, while
    1-of-4 replicas is KILLED and another SLOWED mid-run — with and
    without the mitigation stack (docs/serving.md "Surviving
    overload"):

    * **baseline**: the PR 13 router as-was — redistribute-on-death
      only; hedging off, breakers off, no autoscaler, no ladder.
    * **mitigated**: hedged prefills route around the slow replica,
      breakers fast-fail it, the SLO-burn autoscaler replaces the dead
      replica (and may add more / engage the degradation ladder when
      burn stays high).

    The committed artifact pins: mitigated TTFT attainment >= 2x the
    baseline under identical chaos, ZERO byte-identity regressions on
    surviving streams (a degraded stream must equal its un-degraded
    PREFIX — rungs only clamp budgets, never perturb bytes), zero
    post-warmup recompiles (compile_watch; the autoscaler's replicas
    share the compile cache), and every shed/failed request receiving
    a STRUCTURED error (JSON body over HTTP — status + cause +
    retry_after for sheds; never a hang, never a stdlib HTML page)."""
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.resilience import faults
    from ml_trainer_tpu.serving import (
        Autoscaler, AutoscalerConfig, Router, Server, SloPolicy,
    )
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )
    from ml_trainer_tpu.serving.slo import aggregate_timelines
    from ml_trainer_tpu.telemetry import compile_watch

    model = get_model("gpt2_tiny", max_len=256)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    rng = np.random.default_rng(seed)
    prefixes = [
        rng.integers(0, model.vocab_size, shared_len).astype(np.int32)
        for _ in range(n_tenants)
    ]
    arrivals = np.cumsum(rng.exponential(mean_interarrival, n_requests))
    trace = []
    for i in range(n_requests):
        t = int(rng.integers(0, n_tenants))
        if rng.random() < shared_frac:
            suffix = rng.integers(
                0, model.vocab_size, int(rng.integers(4, 17))
            ).astype(np.int32)
            prompt = np.concatenate([prefixes[t], suffix])
        else:
            prompt = rng.integers(
                0, model.vocab_size, int(rng.integers(16, 33))
            ).astype(np.int32)
        trace.append(ScheduledRequest(
            arrival_s=float(arrivals[i]), tenant=f"tenant{t}",
            prompt=prompt,
            max_new_tokens=int(rng.choice([8, 48], p=[0.6, 0.4])),
        ))
    schedule = schedule_from_trace(schedule_to_records(trace))
    policy = SloPolicy(ttft_ms=ttft_ms, tpot_ms=tpot_ms,
                       target=slo_target)
    pool_pages = pool_factor * max_batch * (256 // page_size) + 1
    server_kwargs = dict(
        max_batch=max_batch, kv_page_size=page_size,
        kv_pages=pool_pages, max_queue=2 * n_requests,
    )
    compile_watch.install()

    def build_router(mitigated: bool) -> Router:
        rk = {"slo": policy, "slo_timelines": 4 * n_requests}
        if mitigated:
            # Aggressive hedge clock: the chaos leg's whole point is
            # routing around a straggler fast.
            rk.update(hedge_quantile=0.9, hedge_factor=1.2,
                      hedge_min_s=0.05)
        else:
            rk.update(hedging=False, breaker_threshold=None)
        return Router.build(
            model, variables,
            roles=["prefill", "prefill", "decode", "decode"],
            router_kwargs=rk, **server_kwargs,
        )

    # Fleet indices are sorted-name order: decode0=0, decode1=1,
    # prefill0=2, prefill1=3.  Kill decode1, slow prefill0 — one dead,
    # one straggling, out of four.
    chaos_spec = (
        f"replica_kill@step=4,host=1;"
        f"replica_slow@step=1,host=2,secs={slow_secs}"
    )

    def warm_continuation_buckets():
        """Chaos shifts prefix-hit lengths (a redistribute-resume
        prefills prompt+committed tokens against a survivor's cache),
        so suffix buckets can appear mid-run that no replay pass
        visited.  Compile every plausible continuation bucket (8..128)
        up front — the compile cache is process-wide and keyed on the
        shared paged-model clone, so all legs (and the autoscaler's
        mid-run replicas) inherit them."""
        from ml_trainer_tpu.serving.engine import SlotDecodeEngine
        from ml_trainer_tpu.serving.scheduler import Request as _Req

        eng = SlotDecodeEngine(
            model, variables, max_batch=max_batch,
            kv_page_size=page_size, kv_pages=pool_pages,
        )
        wrng = np.random.default_rng(10_000 + seed)
        base = wrng.integers(0, model.vocab_size, 160).astype(np.int32)
        for k in (1, 1, 9, 17, 33, 65):  # first k=1 primes the trie
            prompt = np.concatenate([
                base, wrng.integers(0, model.vocab_size, k).astype(np.int32)
            ])
            req = _Req(prompt=prompt, max_new_tokens=2)
            if eng.admit(req, 0) == "active":
                while eng.active_count():
                    eng.step()

    warm_continuation_buckets()

    # Reference pass (no chaos): warms every compile (prefill buckets,
    # decode, kv export/import) AND records each request's un-degraded
    # output — the byte-identity anchor for the chaos legs.
    with build_router(mitigated=True) as router:
        host, port = router.serve_http(port=0)
        url = f"http://{host}:{port}"
        run_open_loop(schedule, url=url, time_scale=0.0)
        ref_run = run_open_loop(schedule, url=url, collect_tokens=True)
    refs = [r.get("output") for r in ref_run["per_request"]]
    if any(o is None for o in refs):
        raise RuntimeError(
            f"reference pass failed: {ref_run['n_errors']} error(s): "
            f"{ref_run['errors']}"
        )

    def run_leg(mitigated: bool) -> dict:
        router = build_router(mitigated)
        autoscaler = None
        if mitigated:
            autoscaler = Autoscaler(
                router,
                lambda role: Server(model, variables, role=role,
                                    **server_kwargs),
                AutoscalerConfig(
                    poll_interval_s=0.25, window_s=6.0,
                    min_window_requests=6, burn_high=1.5,
                    high_polls=2, cooldown_s=2.0, max_replicas=6,
                    min_prefill=2, min_decode=2, scale_down=False,
                ),
            ).start()
        err = None
        try:
            host, port = router.serve_http(port=0)
            url = f"http://{host}:{port}"
            # One untimed fault-free pass AT REAL TIME: prefix caches,
            # replica health and the hedging clock to steady state —
            # chaos hits a WARM fleet, and the hedge clock reflects
            # healthy first-result latency, not compressed-burst queues.
            run_open_loop(schedule, url=url)
            timed_t0 = time.monotonic()
            with faults.injected(chaos_spec):
                try:
                    with compile_watch.expect_no_compiles(
                        f"serve-chaos {'mitigated' if mitigated else 'baseline'}"
                    ):
                        client = run_open_loop(
                            schedule, url=url, collect_tokens=True,
                            timeout=180.0,
                        )
                except AssertionError as e:
                    err = str(e)
                    client = run_open_loop(
                        schedule, url=url, collect_tokens=True,
                        timeout=180.0,
                    )
            server_side = aggregate_timelines(
                router.slo.timelines(since=timed_t0), policy
            )
            snap = router.snapshot()
            asc_summary = (
                autoscaler.summary() if autoscaler is not None else None
            )
        finally:
            if autoscaler is not None:
                autoscaler.close()
            router.close()
        # Byte identity on surviving streams: a completed (possibly
        # budget-clamped) output must equal its un-degraded PREFIX.
        identity_bad = 0
        for row, ref in zip(client["per_request"], refs):
            out = row.get("output")
            if not row["ok"] or out is None:
                continue
            if len(out) > len(ref) or out != ref[: len(out)]:
                identity_bad += 1
        # Structured-failure audit: every failed row must carry a JSON
        # error body (status + cause), sheds a retry_after.
        failed = [r for r in client["per_request"] if not r["ok"]]
        unstructured = [
            r for r in failed
            if not (r.get("structured") or "retry after" in (r.get("error") or ""))
        ]
        leg = {
            "mitigated": mitigated,
            "tokens_per_sec": client["tokens_per_sec"],
            "makespan_s": client["makespan_s"],
            "n_completed": client["n_completed"],
            "n_errors": client["n_errors"],
            "n_shed": sum(
                1 for r in failed if r.get("retry_after") is not None
            ),
            "unstructured_failures": len(unstructured),
            "identity_regressions": identity_bad,
            "ttft_p50_ms": server_side["ttft_ms"]["p50"],
            "ttft_p99_ms": server_side["ttft_ms"]["p99"],
            "ttft_attainment": server_side["attainment"]["ttft"],
            "tpot_attainment": server_side["attainment"]["tpot"],
            "n_timelines": server_side["n_requests"],
            "migrations": snap["migrations_total"],
            "migrations_corrupt": snap["migrations_corrupt_total"],
            "redistributes": snap["redistributes_total"],
            "hedges": snap["hedges_total"],
            "hedge_wins": snap["hedge_wins_total"],
            "flaps_damped": snap["flaps_damped_total"],
            "shed_total": snap["shed_total"],
            "degradation": snap["degradation"],
            "zero_recompiles": err is None,
        }
        if err is not None:
            leg["recompile_error"] = err
        if asc_summary is not None:
            leg["autoscaler"] = asc_summary
        print(
            f"# serve chaos [{'mitigated' if mitigated else ' baseline'}]: "
            f"TTFT attainment {leg['ttft_attainment']:.3f} "
            f"(p99 {leg['ttft_p99_ms']} ms), "
            f"{leg['n_completed']}/{n_requests} completed, "
            f"{leg['hedges']} hedge(s), {leg['redistributes']} "
            f"redistribute(s), {leg['identity_regressions']} identity "
            f"regression(s)" + ("" if err is None else "  [RECOMPILED]"),
            flush=True,
        )
        return leg

    baseline = run_leg(mitigated=False)
    mitigated = run_leg(mitigated=True)
    ratio = round(
        mitigated["ttft_attainment"] / max(baseline["ttft_attainment"],
                                           0.01), 3
    )
    result = {
        "baseline": baseline,
        "mitigated": mitigated,
        "attainment_ratio": ratio,
        "attainment_win_2x": bool(ratio >= 2.0),
        "byte_identity_ok": (
            baseline["identity_regressions"] == 0
            and mitigated["identity_regressions"] == 0
        ),
        "zero_recompiles": bool(
            baseline["zero_recompiles"] and mitigated["zero_recompiles"]
        ),
        "all_failures_structured": (
            baseline["unstructured_failures"] == 0
            and mitigated["unstructured_failures"] == 0
        ),
        "chaos": chaos_spec,
        "slo": {"ttft_ms": ttft_ms, "tpot_ms": tpot_ms,
                "target": slo_target},
        "n_requests": n_requests,
        "n_tenants": n_tenants,
        "shared_frac": shared_frac,
        "shared_len": shared_len,
        "page_size": page_size,
        "max_batch": max_batch,
        "seed": seed,
        "backend": jax.default_backend(),
        # run_report-style summary: what acted, when, and what it cost.
        "run_report": {
            "fleet": "2 prefill + 2 decode (decode1 killed, "
                     "prefill0 slowed)",
            "mitigations": ["hedged prefills", "circuit breakers",
                            "SLO-burn autoscaler", "degradation ladder"],
            "autoscaler_actions": (
                mitigated.get("autoscaler") or {}
            ).get("counts", {}),
            "ladder_transitions": mitigated["degradation"]["transitions"],
            "attainment": {
                "baseline": baseline["ttft_attainment"],
                "mitigated": mitigated["ttft_attainment"],
                "ratio": ratio,
            },
        },
    }
    if not result["byte_identity_ok"]:
        result["error"] = "surviving streams diverged from reference"
    elif not result["zero_recompiles"]:
        result["error"] = "compiles observed during a chaos leg"
    elif not result["all_failures_structured"]:
        result["error"] = (
            f"unstructured failures: baseline "
            f"{baseline['unstructured_failures']}, mitigated "
            f"{mitigated['unstructured_failures']}"
        )
    elif not result["attainment_win_2x"]:
        result["error"] = (
            f"mitigated attainment only {ratio}x baseline (need >= 2x)"
        )
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fp:
            json.dump(result, fp, indent=1)
        print(f"# serve chaos artifact -> {out_path}", flush=True)
    return result


def bench_spec(b=2, pattern_len=8, prompt_len=64, new_tokens=128,
               draft_k=8, reps=2, seed=0):
    """Speculative-decoding leg: tokens/s of the speculative loop
    (n-gram lookup drafter) vs the vanilla compiled decode loop, same
    model, same greedy workload.

    The workload is the one lookup drafting is FOR: a repetitive prompt
    (a short token pattern tiled to ``prompt_len``), greedy decoding.
    Greedy decode collapses into cycles quickly, and once a cycle is in
    the history the n-gram drafter predicts it almost perfectly —
    acceptance approaches K and each verify forward commits ~K+1
    tokens.  The model is ``gpt2_mini`` (≈29M params): big enough that
    a decode forward is weight-streaming-bound, so the K+1-token verify
    window costs ~2x a single-token step, not K+1x — the regime
    speculative decoding exists for (a gpt2_tiny-sized model is
    activation-bound and gains nothing).  Vanilla runs ONE compiled
    lax.scan (its best case: no per-token host dispatch at all), so the
    measured win is forwards saved, not dispatch saved.  Outputs are
    asserted byte-identical before timing — a speedup on wrong tokens
    is not a speedup.  Returns the JSON row (tokens/s both paths,
    speedup, acceptance histogram)."""
    from ml_trainer_tpu.generate import generate
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.speculative import speculative_generate

    model = get_model("gpt2_mini")
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False,
    )
    rng = np.random.default_rng(seed)
    pattern = rng.integers(0, model.vocab_size, pattern_len)
    prompt = jnp.asarray(
        np.stack([
            np.tile(np.roll(pattern, i), prompt_len // pattern_len)
            for i in range(b)
        ]),
        jnp.int32,
    )

    ref = generate(model, variables, prompt, new_tokens)  # compile + warm
    out, stats = speculative_generate(
        model, variables, prompt, new_tokens, draft_k=draft_k,
        return_stats=True,
    )  # compile + warm
    identical = bool(np.array_equal(np.asarray(out), np.asarray(ref)))

    def timed(fn):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    t_van = timed(lambda: generate(model, variables, prompt, new_tokens))
    t_spec = timed(lambda: speculative_generate(
        model, variables, prompt, new_tokens, draft_k=draft_k,
    ))
    total = b * new_tokens
    van_tps = total / t_van
    spec_tps = total / t_spec
    print(f"# spec vanilla: {van_tps:,.1f} tokens/s", flush=True)
    print(
        f"# spec speculative (K={draft_k}, ngram lookup): "
        f"{spec_tps:,.1f} tokens/s ({spec_tps / van_tps:.2f}x vanilla, "
        f"acceptance {stats['acceptance_rate']:.2f}, "
        f"{stats['tokens_per_step']:.2f} tokens/verify-step)", flush=True,
    )
    return {
        "model": "gpt2_mini",
        "batch": b,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "draft_k": draft_k,
        "drafter": "ngram",
        "greedy_identical": identical,
        "vanilla_tokens_per_sec": round(van_tps, 1),
        "spec_tokens_per_sec": round(spec_tps, 1),
        "speedup": round(spec_tps / van_tps, 2),
        "acceptance_rate": round(stats["acceptance_rate"], 4),
        "tokens_per_verify_step": round(stats["tokens_per_step"], 3),
        "accept_hist": stats["accept_hist"],
        "backend": jax.default_backend(),
    }


def bench_dispatch(iters=300):
    """pjit dispatch microbenchmark: per-call host overhead of the
    compiled train and decode steps, measured on programs whose
    EXECUTION is microseconds — so the wall clock per call is dominated
    by dispatch (argument flattening, executable lookup, transfer
    setup).  A compile-cache or dispatch-path regression moves these
    numbers far before it moves a real workload's throughput."""
    import statistics as _stats

    from ml_trainer_tpu.models import get_model

    model = get_model("gpt2_tiny", max_len=32, depth=1, embed_dim=32,
                      num_heads=2)
    x = jnp.zeros((1, 1), jnp.int32)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, x, train=False
    )
    params = variables["params"]

    # Decode-shaped step: one forward + argmax, state threaded.
    @jax.jit
    def decode_step(p, tok):
        logits = model.apply({"params": p}, tok, train=False)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]

    # Train-shaped step: loss + grad + SGD update, donated params.
    def loss_fn(p, tok):
        logits = model.apply({"params": p}, tok, train=True)
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    @jax.jit
    def train_step(p, tok):
        grads = jax.grad(loss_fn)(p, tok)
        return jax.tree.map(lambda a, g: a - 1e-3 * g, p, grads)

    def per_call(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
        return _stats.median(times) * 1e6  # µs

    decode_us = per_call(decode_step, params, x)
    train_us = per_call(train_step, params, x)
    print(f"# dispatch decode step: {decode_us:,.1f} µs/call", flush=True)
    print(f"# dispatch train step:  {train_us:,.1f} µs/call", flush=True)
    return {
        "decode_step_us_per_call": round(decode_us, 1),
        "train_step_us_per_call": round(train_us, 1),
        "iters": iters,
        "backend": jax.default_backend(),
    }


def _chip_peak_flops() -> float:
    """Peak bf16 FLOPs/s of one local chip — the MFU denominator.
    Owned by the telemetry spine now (telemetry/flops.py) so the bench,
    the MFU ledger, and live training telemetry can never disagree on
    the peak table; this alias keeps older callers working."""
    from ml_trainer_tpu.telemetry.flops import chip_peak_flops

    return chip_peak_flops()


def _compiled_flops(compiled) -> float | None:
    """FLOPs of ONE compiled train step via XLA cost analysis (measured on
    the actual executable, not an analytic formula).  None if unavailable."""
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        flops = float(analysis.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


# One row per model: (batch shape, task kind, constructor kwargs-builder).
# kwargs are built lazily (jnp.bfloat16 needs jax at call time, and keeping
# everything in one table means a new model cannot be half-registered).
EXTENDED_CONFIGS = {
    # The parity workload's model as a --one row (CPU-cheap): the
    # resilience acceptance gate compares `--one mlmodel` across commits
    # to prove the nonfinite guard adds no measurable step cost.
    "mlmodel": ((32, 32, 32, 3), "image", lambda: dict()),
    "resnet50": ((32, 224, 224, 3), "image", lambda: dict(dtype=jnp.bfloat16)),
    "vit_b16": ((32, 224, 224, 3), "image",
                lambda: dict(num_classes=1000, dtype=jnp.bfloat16)),
    "bert_base": ((32, 128), "tokens",
                  lambda: dict(num_classes=2, dtype=jnp.bfloat16)),
    # loss_chunk: bench the trainer's REAL GPT-2 path — the chunked
    # weight-tied LM loss that never materializes the [B, S, V] logits
    # (~0.8 GB at bs=8); the full-logits + criterion path is not how the
    # Trainer runs this model.
    "gpt2": ((8, 1024), "lm",
             lambda: dict(dtype=jnp.bfloat16, loss_chunk=128)),
}


def bench_one_model(name: str, batch_size: int | None = None) -> dict:
    """One north-star model: one full train step (bf16 compute, f32
    params), steady-state samples/sec + MFU (achieved FLOPs / chip peak).

    ``batch_size`` overrides the table's leading batch dim — the MFU
    ledger runs ResNet-50 at 32/128/256 to show where the MXU saturates.

    Everything device-touching is jitted: flax ``init`` executes EAGERLY
    by default, one dispatch per op; ``jax.jit(model.init)`` makes it one
    compile + one execution."""
    import optax

    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.ops import get_criterion, get_optimizer
    from ml_trainer_tpu.train_state import TrainState

    def progress(msg):
        print(f"# {name}: {msg}", file=sys.stderr, flush=True)

    bf16 = jnp.bfloat16
    shape, kind, make_kw = EXTENDED_CONFIGS[name]
    if batch_size is not None:
        shape = (batch_size,) + tuple(shape[1:])
    model = get_model(name, **make_kw())
    rng = np.random.default_rng(0)
    progress("transferring inputs to device")
    if kind == "image":
        x = jnp.asarray(rng.normal(size=shape), bf16)
        y = jnp.asarray(rng.integers(0, 10, shape[0]), jnp.int32)
    else:
        x = jnp.asarray(rng.integers(0, 1000, shape), jnp.int32)
        y = (
            jnp.roll(x, -1, axis=1)
            if kind == "lm"
            else jnp.asarray(rng.integers(0, 2, shape[0]), jnp.int32)
        )
    jax.block_until_ready((x, y))
    progress("inputs on device; compiling init")

    t_c = time.time()
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0)}, x, train=False
    )
    print(f"# {name}: init in {time.time() - t_c:.0f}s",
          file=sys.stderr, flush=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = get_optimizer("adamw", 1e-4)
    criterion = get_criterion("cross_entropy")
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=jax.jit(tx.init)(params), batch_stats=batch_stats,
        rng=jax.random.PRNGKey(1),
    )
    has_bs = bool(batch_stats)

    # Models carrying an active loss_chunk compute their own loss inside
    # the forward (chunked LM head) — same contract the Trainer uses.
    takes_targets = bool(getattr(model, "loss_chunk", 0))

    def step(state, x, y):
        def loss_fn(p):
            if takes_targets:
                loss = model.apply({"params": p}, x, train=True, targets=y)
                return loss, state.batch_stats
            if has_bs:
                out, mut = model.apply(
                    {"params": p, "batch_stats": state.batch_stats},
                    x, train=True, mutable=["batch_stats"],
                )
                return criterion(out, y), mut["batch_stats"]
            out = model.apply({"params": p}, x, train=True)
            return criterion(out, y), state.batch_stats

        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        updates, opt_state = tx.update(
            grads, state.opt_state, state.params
        )
        return (
            state.replace(
                step=state.step + 1,
                params=optax.apply_updates(state.params, updates),
                opt_state=opt_state,
                batch_stats=new_bs,
            ),
            loss,
        )

    # Compile ONCE; the same executable feeds the FLOPs analysis and the
    # timing loop.  The state is donated: the timing loop
    # rebinds it every call, and without donation every step allocates a
    # second copy of params+moments before freeing the old one.
    step = jax.jit(step, donate_argnums=0)
    t_c = time.time()
    compiled = step.lower(state, x, y).compile()
    print(f"# {name}: compiled in {time.time() - t_c:.0f}s",
          file=sys.stderr, flush=True)
    # FLOPs: XLA's measured cost analysis when the executable exposes
    # it, else the telemetry spine's analytic accounting — the SAME
    # accounting the trainer's live MFU line uses (telemetry/flops.py).
    flops = _compiled_flops(compiled)
    flops_source = "xla"
    if flops is None:
        from ml_trainer_tpu.telemetry.flops import train_step_flops

        flops = train_step_flops(model, shape)
        flops_source = "analytic"
    rate, state = _steady_state_rate(
        compiled, state, [(x, y)], warmup=3, iters=20
    )
    # Step-time distribution: a short FENCED per-step pass (StepTimer
    # record_steps) — the mean above keeps dispatch pipelining live, the
    # percentiles pay one fence per step for an honest tail.
    ptimer = StepTimer(warmup=2, record_steps=True)
    for _ in range(12):
        state, loss = compiled(state, x, y)
        ptimer.tick(loss, 1)
    p50, p99 = ptimer.p50(), ptimer.p99()
    # MFU only means something against the real chip's peak.
    stamp = _device_stamp()
    on_tpu = stamp["platform"] == "tpu"
    mfu = rate * flops / _chip_peak_flops() if (flops and on_tpu) else None
    # HBM columns (telemetry/memory.py): the LIVE per-device peak (TPU
    # allocator stats; live-array accounting on CPU, which cannot see
    # XLA's scratch arena) beside the ANALYTIC ledger's peak prediction.
    from ml_trainer_tpu.telemetry import memory as _memory

    mem_live = _memory.live_memory_snapshot()
    mem_ledger = _memory.bench_step_ledger(state, model, (x, y))
    return {
        "model": name, "batch_shape": list(shape),
        "samples_per_sec": round(rate * shape[0], 1),
        "step_ms_p50": round(p50 * 1e3, 3) if p50 is not None else None,
        "step_ms_p99": round(p99 * 1e3, 3) if p99 is not None else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "flops_per_step": flops,
        "flops_source": flops_source if flops else None,
        "peak_hbm_bytes": int(mem_live["max_peak_bytes_in_use"]),
        "peak_hbm_source": mem_live["source"],
        "analytic_hbm_bytes": int(mem_ledger.peak_bytes()),
        "analytic_hbm_resident_bytes": int(mem_ledger.resident_bytes()),
        **stamp,
    }


def bench_chaos(size=2048, batch_size=32, save_every=8, preempt_step=41,
                epochs=1):
    """Chaos leg: the measurable cost of resilience (CPU-safe, tiny model).

    Three numbers a preemptible-fleet operator budgets around:

    * ``ckpt_overhead_pct`` — wall-clock overhead of step-granular
      checkpoints (``save_every_steps``) vs the same epoch without them
      (the async writer should hide most of the I/O);
    * ``steps_lost_on_preempt`` — training steps between the last
      committed step checkpoint and the preemption point (bounded by
      ``save_every_steps - 1``);
    * ``time_to_recover_secs`` — wall clock for ``fit(resume=True)`` to
      restore the emergency checkpoint and finish the interrupted epoch.
    """
    import os
    import shutil
    import tempfile

    from ml_trainer_tpu import Trainer, MLModel
    from ml_trainer_tpu.data import SyntheticCIFAR10
    from ml_trainer_tpu.resilience import faults
    from ml_trainer_tpu import checkpoint as ckpt

    def fresh(model_dir, **kw):
        return Trainer(
            MLModel(),
            datasets=(SyntheticCIFAR10(size=size, seed=0),
                      SyntheticCIFAR10(size=256, seed=1)),
            epochs=epochs, batch_size=batch_size, model_dir=model_dir,
            metric=None, lr=0.01, **kw,
        )

    dirs = [tempfile.mkdtemp(prefix="bench_chaos_") for _ in range(4)]
    try:
        # Warmup run: pays one-time costs (first-touch numpy/XLA paths)
        # so the base-vs-checkpointed comparison is order-independent.
        fresh(dirs[3]).fit()
        # 1. checkpoint-save overhead: same epoch with/without step saves.
        t0 = time.perf_counter()
        fresh(dirs[0]).fit()
        base_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        fresh(dirs[1], save_every_steps=save_every).fit()
        ckpt.wait_for_checkpoints()
        ckpt_s = time.perf_counter() - t0
        overhead_pct = (ckpt_s / base_s - 1.0) * 100.0
        print(f"# chaos ckpt overhead: {base_s:.2f}s -> {ckpt_s:.2f}s "
              f"({overhead_pct:+.1f}% with save_every_steps={save_every})",
              flush=True)

        # 2. preemption: inject at a step between two step-checkpoints.
        with faults.injected(f"preempt@step={preempt_step}"):
            t = fresh(dirs[2], save_every_steps=save_every)
            t.fit()
        assert t.preempted, "preempt fault did not fire"
        latest = ckpt.latest_valid_checkpoint(
            os.path.join(dirs[2], "checkpoints")
        )
        _, hist, _ = ckpt.restore_checkpoint(
            latest, ckpt.fetch_to_host(t.state)
        )
        saved_step = hist.get("mid_epoch", {}).get("batches_done", 0)
        # The emergency save checkpoints the preemption step itself, so
        # steps re-trained on resume measure the NO-emergency floor: the
        # cadence gap a hard-kill (no clean exit) would lose.
        cadence_lost = preempt_step - (
            preempt_step // save_every
        ) * save_every
        print(f"# chaos preempt at step {preempt_step}: emergency save at "
              f"batch {saved_step}, steps lost 0 (clean exit) / "
              f"{cadence_lost} (hard kill, cadence {save_every})",
              flush=True)

        # 3. time-to-recover: resume and finish the interrupted epoch.
        t0 = time.perf_counter()
        r = fresh(dirs[2], save_every_steps=save_every)
        r.fit(resume=True)
        recover_s = time.perf_counter() - t0
        print(f"# chaos time-to-recover: {recover_s:.2f}s "
              f"(restore + {size // batch_size - saved_step} remaining "
              "step(s) + validation)", flush=True)
        return {
            "ckpt_overhead_pct": round(overhead_pct, 1),
            "base_epoch_secs": round(base_s, 2),
            "ckpt_epoch_secs": round(ckpt_s, 2),
            "save_every_steps": save_every,
            "preempt_step": preempt_step,
            "emergency_saved_at_batch": saved_step,
            "steps_lost_clean_exit": 0,
            "steps_lost_hard_kill": cadence_lost,
            "time_to_recover_secs": round(recover_s, 2),
            "resumed_epochs": r.history["epochs"],
            "backend": jax.default_backend(),
        }
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)


def bench_elastic():
    """Elastic chaos leg: drive ``scripts/elastic_smoke.py`` (its phases
    need their own processes for per-phase virtual device counts) and
    distill the numbers a preemptible-fleet operator budgets around:

    * the in-process drain→reshape→continue downtime and steps-lost
      (clean-drain path: a ``host_kill`` fault drops 1 of 2 simulated
      hosts, 8 -> 4 devices, same ``fit()`` call finishes with the
      uninterrupted trajectory);
    * the hard-kill restart path: a real 2-process cluster loses a host
      to ``os._exit`` with NO emergency checkpoint, and the restart at
      a different topology is bounded by the ``save_every_steps``
      cadence — ``time_to_recover_secs`` is its wall-clock.

    The committed ``docs/elastic_chaos_cpu.json`` pins these; the
    fastlane gate (``scripts/bench_gate.py gate_elastic``) hard-fails
    the invariants and ratchets the recovery rate.
    """
    import os
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts",
        "elastic_smoke.py",
    )
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=500, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    line = next(
        (ln for ln in proc.stdout.splitlines()
         if ln.startswith("ELASTIC_SMOKE_RESULT ")), None,
    )
    if proc.returncode != 0 or line is None:
        tail = (proc.stdout + proc.stderr).strip().splitlines()[-8:]
        return {"ok": False, "error": " | ".join(tail)}
    result = json.loads(line[len("ELASTIC_SMOKE_RESULT "):])
    ip, rs = result["in_process"], result.get("restart", {})
    out = {
        "ok": result["ok"],
        "reshape_downtime_secs": ip["reshape_downtime_secs"],
        "steps_lost_clean_drain": ip["steps_lost"],
        "trajectory_equal": ip["trajectory_equal"],
        "bit_exact_resumable": ip["bit_exact_resumable"],
        "old_topology": ip["old_topology"],
        "new_topology": ip["new_topology"],
        "backend": jax.default_backend(),
    }
    if rs:
        out.update(
            steps_lost_hard_kill=rs["steps_lost"],
            steps_lost_bound=rs["steps_lost_bound"],
            time_to_recover_secs=rs["time_to_recover_secs"],
        )
    print(
        f"# elastic: reshape {out['old_topology']} -> "
        f"{out['new_topology']} in {out['reshape_downtime_secs']}s, "
        f"hard-kill restart lost {out.get('steps_lost_hard_kill', '?')} "
        f"step(s), recovered in "
        f"{out.get('time_to_recover_secs', '?')}s", flush=True,
    )
    return out


def bench_telemetry(batch_size=32, reps=3, warmup=5, iters=40):
    """Telemetry-overhead leg: the instrumented train step (on-device
    grad/param/update-norm stats, Trainer(telemetry=True)) vs the bare
    step, same model, same pre-materialized device batches.

    The claim under test (docs/observability.md): step telemetry rides
    INSIDE the one compiled program — no extra dispatches, no host
    syncs — so its cost is a few reductions, targeted at <2% step time
    even on the dispatch-bound CPU LeNet row (on a real chip the norms
    vanish into the step).  Interleaves ``reps`` measurement passes of
    each variant and takes each side's best rate, the standard
    noise-floor trick for single-digit-percent comparisons."""
    from ml_trainer_tpu import Trainer, MLModel
    from ml_trainer_tpu.data import SyntheticCIFAR10, prefetch_to_device
    from ml_trainer_tpu.utils.functions import custom_pre_process_function

    def make(telemetry):
        ds = SyntheticCIFAR10(
            size=PARITY_DS_SIZE, transform=custom_pre_process_function()
        )
        return Trainer(
            MLModel(), datasets=(ds, ds), epochs=1, batch_size=batch_size,
            model_dir="/tmp/bench_telemetry", metric="accuracy", lr=0.01,
            telemetry=telemetry,
        )

    def batches_for(trainer):
        return [
            (x, y, jnp.asarray(1.0, jnp.float32))
            for _, (x, y) in zip(
                range(16),
                prefetch_to_device(
                    trainer.train_loader, size=2,
                    sharding=trainer._batch_sharding,
                ),
            )
        ]

    bare = make(False)
    instr = make(True)
    bare_batches = batches_for(bare)
    instr_batches = batches_for(instr)
    best = {"bare": 0.0, "telemetry": 0.0}
    state_bare, state_instr = bare.state, instr.state
    for _ in range(reps):
        r, state_bare = _steady_state_rate(
            bare._train_step, state_bare, bare_batches,
            warmup=warmup, iters=iters,
        )
        best["bare"] = max(best["bare"], r)
        r, state_instr = _steady_state_rate(
            instr._train_step, state_instr, instr_batches,
            warmup=warmup, iters=iters,
        )
        best["telemetry"] = max(best["telemetry"], r)
    bare_sps = best["bare"] * batch_size
    instr_sps = best["telemetry"] * batch_size
    overhead_pct = (bare_sps / instr_sps - 1.0) * 100.0
    # Proof of the no-extra-programs claim, in the artifact itself.
    compiles = {
        "bare": bare._train_step._cache_size(),
        "telemetry": instr._train_step._cache_size(),
    }
    print(f"# telemetry bare:         {bare_sps:,.1f} samples/s", flush=True)
    print(f"# telemetry instrumented: {instr_sps:,.1f} samples/s "
          f"({overhead_pct:+.2f}% step-time overhead, "
          f"{compiles['telemetry']} compiled program(s))", flush=True)
    return {
        "model": "mlmodel",
        "batch_size": batch_size,
        "bare_samples_per_sec": round(bare_sps, 1),
        "telemetry_samples_per_sec": round(instr_sps, 1),
        "overhead_pct": round(overhead_pct, 2),
        "target_overhead_pct": 2.0,
        "compiled_programs": compiles,
        "backend": jax.default_backend(),
    }


def bench_mixed(n_devices=8, batch_size=16, seq_len=32, iters=8, warmup=2,
                reps=2, out_path=None):
    """Mixed-precision / sharded-update matrix on a virtual pure-DP mesh
    (the ``dryrun_multichip`` style: CPU with forced host devices, same
    compiled collectives as the chip):

        {fp32, bf16} x {fused-psum, bucketed reduce-scatter + sharded
        update}

    Each row is the REAL ``Trainer`` train step (the exact code path of
    training runs) on pre-materialized device batches: steady-state step
    time, per-op comm bytes (analytic, trace-time), the per-bucket
    reduce-scatter/all-gather breakdown for the sharded rows, and the
    compiled-program-count pin.  Needs ``n_devices`` local devices; when
    fewer exist the measurement respawns itself in a subprocess with
    ``--xla_force_host_platform_device_count`` (the backend's device
    count is fixed at init)."""
    import os
    import subprocess

    if len(jax.devices()) < n_devices:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
        env["ML_TRAINER_TPU_MIXED_CHILD"] = "1"
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mixed",
             "--mixed-devices", str(n_devices)],
            env=env, capture_output=True, text=True, timeout=1500,
        )
        result = None
        for line in r.stdout.splitlines():
            print(line, flush=True)  # re-surface the child's rows
            if line.startswith("{"):
                try:
                    result = json.loads(line).get("mixed")
                except ValueError:
                    pass
        if r.returncode != 0 or result is None:
            tail = (r.stderr or "").strip().splitlines()
            return {"error": f"mixed worker failed (rc={r.returncode}): "
                             f"{tail[-1] if tail else 'no stderr'}"}
        if out_path:
            _write_mixed_artifact(result, out_path)
        return result

    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.data import SyntheticTokens, prefetch_to_device
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.parallel.comm_stats import (
        comm_bucket_bytes,
        comm_bytes,
        reset_comm_stats,
    )

    ds = SyntheticTokens(
        size=max(batch_size * 8, 64), seq_len=seq_len, vocab_size=256,
        seed=0,
    )
    rows = []
    for precision in ("fp32", "bf16"):
        for dp_update in ("fused", "sharded"):
            reset_comm_stats()
            trainer = Trainer(
                get_model("gpt2_tiny", vocab_size=256),
                datasets=(ds, ds), epochs=1, batch_size=batch_size,
                model_dir=f"/tmp/bench_mixed_{precision}_{dp_update}",
                mesh_shape={"data": n_devices}, optimizer="adamw",
                metric=None, lr=1e-3, precision=precision,
                dp_update=dp_update, bucket_mb=0.25,
            )
            batches = [
                (x, y, jnp.asarray(1.0, jnp.float32))
                for _, (x, y) in zip(
                    range(4),
                    prefetch_to_device(
                        trainer.train_loader, size=2,
                        sharding=trainer._batch_sharding,
                    ),
                )
            ]
            # One probed step first: finite-loss evidence (the state it
            # returns replaces the donated input).
            state, loss, *_ = trainer._train_step(
                trainer.state, *batches[0]
            )
            loss = float(loss)
            best = 0.0
            for _ in range(reps):
                r, state = _steady_state_rate(
                    trainer._train_step, state, batches,
                    warmup=warmup, iters=iters,
                )
                best = max(best, r)
            comm = {k: round(v, 1) for k, v in comm_bytes().items()}
            buckets = {
                op: {b: round(v, 1) for b, v in bs.items()}
                for op, bs in comm_bucket_bytes().items()
            }
            row = {
                "precision": precision,
                "dp_update": dp_update,
                "samples_per_sec": round(best * batch_size, 1),
                "step_ms": round(1e3 / best, 3) if best else None,
                "loss": round(loss, 4),
                "loss_finite": bool(np.isfinite(loss)),
                "comm_bytes": comm,
                "comm_buckets": buckets,
                "compiled_programs_constant":
                    trainer._train_step._cache_size() == 1,
            }
            if dp_update == "sharded":
                row["n_buckets"] = len(trainer._bucket_plan.buckets)
                row["overlap_fraction"] = round(
                    trainer._bucket_plan.overlap_fraction, 4
                )
            rows.append(row)
            print(
                f"# mixed {precision:>4}/{dp_update:<7} "
                f"{row['samples_per_sec']:>8,.1f} samples/s  "
                f"step {row['step_ms']:.2f} ms  loss {loss:.4f}  "
                f"comm {sum(comm.values()):,.0f} B/step", flush=True,
            )

    def rate(precision, dp_update):
        for row in rows:
            if (row["precision"], row["dp_update"]) == (precision, dp_update):
                return row["samples_per_sec"]
        return 0.0

    result = {
        "model": "gpt2_tiny(vocab=256)",
        "n_devices": n_devices,
        "batch_size": batch_size,
        "seq_len": seq_len,
        "backend": jax.default_backend(),
        "rows": rows,
        # Headline ratios: the sharded-update win at each precision, and
        # the full-stack bf16+sharded vs the fp32 fused baseline.
        "sharded_vs_fused_fp32": round(
            rate("fp32", "sharded") / max(rate("fp32", "fused"), 1e-9), 3
        ),
        "sharded_vs_fused_bf16": round(
            rate("bf16", "sharded") / max(rate("bf16", "fused"), 1e-9), 3
        ),
        "bf16_sharded_vs_fp32_fused": round(
            rate("bf16", "sharded") / max(rate("fp32", "fused"), 1e-9), 3
        ),
    }
    if out_path:
        _write_mixed_artifact(result, out_path)
    return result


def _write_mixed_artifact(result, out_path) -> None:
    import os

    payload = dict(result)
    payload["generated_by"] = "bench.py --mixed"
    payload["date"] = _utcnow()
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fp:
        json.dump(payload, fp, indent=1)
    os.replace(tmp, out_path)
    print(f"# mixed artifact -> {out_path}", flush=True)


PIPELINE_MATRIX = (
    # (n_stage_devices, n_virtual, n_micro, schedule, remat)
    (2, 1, 4, "gpipe", False),
    (2, 1, 4, "1f1b", False),
    (4, 1, 8, "gpipe", False),       # the S=4/M=8 acceptance pair
    (4, 1, 8, "1f1b", False),
    (4, 1, 8, "zb", False),
    (4, 2, 8, "interleaved", False),  # 8 virtual stages on 4 devices
    (4, 1, 8, "gpipe", True),        # memory-bounded pair: gpipe remat
    (4, 1, 8, "1f1b", True),         # vs 1F1B's O(S) combined backward
)


def bench_pipeline(n_devices=4, width=64, mb_rows=8, iters=20, warmup=5,
                   reps=2, out_path=None):
    """Pipeline-schedule matrix on a virtual ``stage`` mesh (the
    ``dryrun_multichip`` style: CPU with forced host devices, same
    compiled collectives as the chip): one jitted ``value_and_grad`` of
    a pipelined stage stack per row — the schedule engine itself, no
    trainer machinery in the timed region.

    Each row records the fenced steady-state step time, the analytic
    tick-table facts (bubble fraction, executed-compute waste, stash
    sizing from ``pipeline_schedule_info``), the per-hop comm bytes
    (``comm_bytes_by_hop{schedule=,hop=}``), a trajectory-equality check
    against the serial fold (value AND grad), and the compiled-program
    pin.  Headline: the 1F1B-vs-GPipe step-time ratio at S=4/M=8 —
    GPipe's scan executes garbage compute in its bubble slots on every
    device while the tick-table engine skips idle slots, so 1F1B should
    hold or beat it.  Needs ``n_devices`` local devices; with fewer the
    measurement respawns itself in a subprocess with
    ``--xla_force_host_platform_device_count``."""
    import os
    import subprocess

    if len(jax.devices()) < n_devices:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
        env["ML_TRAINER_TPU_PIPELINE_CHILD"] = "1"
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--pipeline",
             "--pipeline-devices", str(n_devices)],
            env=env, capture_output=True, text=True, timeout=1500,
        )
        result = None
        for line in r.stdout.splitlines():
            print(line, flush=True)  # re-surface the child's rows
            if line.startswith("{"):
                try:
                    result = json.loads(line).get("pipeline")
                except ValueError:
                    pass
        if r.returncode != 0 or result is None:
            tail = (r.stderr or "").strip().splitlines()
            return {"error": f"pipeline worker failed (rc={r.returncode}): "
                             f"{tail[-1] if tail else 'no stderr'}"}
        if out_path:
            _write_pipeline_artifact(result, out_path)
        return result

    import numpy as _np

    from ml_trainer_tpu.parallel import create_mesh
    from ml_trainer_tpu.parallel.comm_stats import (
        comm_hop_bytes,
        reset_comm_stats,
    )
    from ml_trainer_tpu.parallel.pipeline import (
        pipeline_apply,
        pipeline_schedule_info,
        reset_pipeline_info,
        stack_stage_params,
    )

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def make_stack(n, seed):
        rng = _np.random.default_rng(seed)
        return stack_stage_params([
            {"w": jnp.asarray(rng.normal(0, 0.5, (width, width)),
                              jnp.float32),
             "b": jnp.asarray(rng.normal(0, 0.1, (width,)), jnp.float32)}
            for _ in range(n)
        ])

    rows = []
    for S, V, M, schedule, remat in PIPELINE_MATRIX:
        if S > n_devices:
            continue
        G = S * V
        mesh = create_mesh({"stage": S}, devices=jax.devices()[:S])
        stacked = make_stack(G, seed=G + M)
        x = jnp.asarray(
            _np.random.default_rng(M + S).normal(size=(M * mb_rows, width)),
            jnp.float32,
        )
        reset_comm_stats()
        reset_pipeline_info()

        @jax.jit
        def vag(p, x=x, mesh=mesh, M=M, schedule=schedule, V=V,
                remat=remat):
            return jax.value_and_grad(lambda pp: jnp.sum(pipeline_apply(
                stage_fn, pp, x, mesh, n_microbatches=M,
                schedule=schedule, n_virtual=V, remat=remat) ** 2))(p)

        v, g = jax.block_until_ready(vag(stacked))
        # Trajectory equality vs the serial fold (value AND grad).
        def serial_loss(p):
            def body(carry, pv):
                return stage_fn(pv, carry), None
            out, _ = jax.lax.scan(body, x, p)
            return jnp.sum(out ** 2)

        vs, gs = jax.value_and_grad(serial_loss)(stacked)
        equal = bool(_np.isclose(float(v), float(vs), rtol=1e-5)) and all(
            _np.allclose(_np.asarray(a), _np.asarray(b), atol=2e-4,
                         rtol=1e-4)
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gs))
        )
        best = None
        for _ in range(reps):
            for _ in range(warmup):
                jax.block_until_ready(vag(stacked))
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(vag(stacked))
            dt = (time.perf_counter() - t0) / iters
            best = dt if best is None else min(best, dt)
        info = pipeline_schedule_info().get(schedule, {})
        hops = {
            h: round(v_, 1)
            for h, v_ in comm_hop_bytes().get(schedule, {}).items()
        }
        row = {
            "schedule": schedule, "n_stage_devices": S, "n_virtual": V,
            "n_stages": G, "n_micro": M, "remat": remat,
            "step_ms": round(best * 1e3, 3),
            "serial_equal": equal,
            "compiled_programs_constant": vag._cache_size() == 1,
            "bubble_fraction": info.get("bubble_fraction"),
            "wasted_compute_fraction": info.get("wasted_compute_fraction"),
            "stash_slots": info.get("stash_slots"),
            "comm_bytes_by_hop": hops,
        }
        rows.append(row)
        print(
            f"# pipeline S={S} V={V} M={M} {schedule:>11}/"
            f"{'remat' if remat else 'store'} {row['step_ms']:>8.3f} ms  "
            f"bubble {row['bubble_fraction']}  "
            f"equal={'Y' if equal else 'N'}", flush=True,
        )

    def step_ms(schedule, S, M, remat=False):
        for row in rows:
            if (row["schedule"], row["n_stage_devices"], row["n_micro"],
                    row["remat"]) == (schedule, S, M, remat):
                return row["step_ms"]
        return None

    g48, f48 = step_ms("gpipe", 4, 8), step_ms("1f1b", 4, 8)
    result = {
        "kind": "pipeline schedule x stages matrix (value_and_grad of a "
                f"{width}-wide tanh stage stack, {mb_rows}-row "
                "microbatches)",
        "n_devices": n_devices,
        "backend": jax.default_backend(),
        "rows": rows,
        # Headline: >1.0 means 1F1B beats GPipe at the acceptance config.
        "gpipe_over_1f1b_s4_m8": (
            round(g48 / f48, 3) if g48 and f48 else None
        ),
        "gpipe_over_1f1b_s4_m8_remat": (
            round((step_ms("gpipe", 4, 8, True) or 0)
                  / step_ms("1f1b", 4, 8, True), 3)
            if step_ms("1f1b", 4, 8, True) else None
        ),
    }
    if out_path:
        _write_pipeline_artifact(result, out_path)
    return result


def _write_pipeline_artifact(result, out_path) -> None:
    import os

    payload = dict(result)
    payload["generated_by"] = "bench.py --pipeline"
    payload["date"] = _utcnow()
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fp:
        json.dump(payload, fp, indent=1)
    os.replace(tmp, out_path)
    print(f"# pipeline artifact -> {out_path}", flush=True)


def bench_kernels(iters=40, warmup=5, reps=2, new_tokens=12,
                  out_path=None):
    """Kernel-layer microbench + decode-path gate evidence for the
    ``ops/kernels/`` Pallas pass (paged-attention decode, fused
    sharded-Adam tail, int8 weight-quantized matmul).

    Each kernel row times its lax reference against the dispatcher's
    ``implementation='auto'`` path on THIS backend.  Off-TPU 'auto'
    resolves to the reference, so the before/after pair converges by
    construction — that is the honest CPU artifact: parity (interpret
    mode, bit-for-bit) and engine byte-identity are the gate, the
    timing columns ratchet the shared program, and the TPU win shows up
    only when the same artifact is regenerated on a chip.  The decode
    leg runs the REAL engine twice (gather+flash vs ``paged_kernel``):
    byte-identical outputs across ragged traffic, steady-state compiled
    decode step time, and the zero-post-warmup-recompile pin."""
    import functools

    import optax

    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.ops.kernels import (
        adam_scalars,
        fused_adam_update,
        int8_matmul,
        paged_attention,
        paged_attention_reference,
        quantize_per_channel,
        unscale_sqsum,
    )
    from ml_trainer_tpu.serving import Server
    from ml_trainer_tpu.serving.engine import SlotDecodeEngine
    from ml_trainer_tpu.telemetry import compile_watch

    backend = jax.default_backend()

    def best_us(fn, *args):
        f = jax.jit(fn)
        jax.block_until_ready(f(*args))  # compile outside the timer
        best = float("inf")
        for _ in range(reps):
            for _ in range(warmup):
                out = f(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = f(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / iters)
        return round(best * 1e6, 2)

    def bits_equal(a, b):
        return bool(all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        ))

    kernels = {}

    # ---- (a) paged-attention decode: gather+attention vs fused kernel.
    b, h, d, P, ps, n_pages = 4, 4, 32, 4, 16, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, d), jnp.float32)
    k_pool = jax.random.normal(kk, (n_pages, h, ps, d), jnp.float32)
    v_pool = jax.random.normal(kv, (n_pages, h, ps, d), jnp.float32)
    table = jax.random.randint(
        jax.random.PRNGKey(7), (b, P), 1, n_pages, jnp.int32
    )
    # Full row, 1-token row, mid-page partial, partial last page.
    lengths = jnp.asarray([P * ps, 1, 17, 40], jnp.int32)
    ref_us = best_us(
        functools.partial(paged_attention, implementation="reference"),
        q, k_pool, v_pool, table, lengths,
    )
    auto_us = best_us(paged_attention, q, k_pool, v_pool, table, lengths)
    parity = bits_equal(
        paged_attention(q, k_pool, v_pool, table, lengths,
                        implementation="pallas", interpret=True),
        paged_attention_reference(q, k_pool, v_pool, table, lengths),
    )
    # Gather-overhead diagnostic: the same attention on PRE-gathered
    # contiguous KV — the delta vs the reference is the per-step copy
    # the fused kernel eliminates on TPU.
    kc = k_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, h, P * ps, d)
    vc = v_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, h, P * ps, d)
    valid = (
        jnp.arange(P * ps)[None, :] < lengths[:, None]
    )[:, None, None, :]

    from ml_trainer_tpu.ops.attention import dot_product_attention

    def contiguous_attn(qv, kx, vx, mask):
        out = dot_product_attention(qv[:, :, None, :], kx, vx, mask=mask)
        return out[:, :, 0, :]

    contig_us = best_us(contiguous_attn, q, kc, vc, valid)
    kernels["paged_attention"] = {
        "shape": {"batch": b, "heads": h, "head_dim": d,
                  "pages_per_seq": P, "page_size": ps},
        "reference_us": ref_us,
        "kernel_us": auto_us,
        "speedup": round(ref_us / max(auto_us, 1e-9), 3),
        "interpret_parity": parity,
        "contiguous_attn_us": contig_us,
        "gather_overhead_fraction": round(
            max(0.0, 1.0 - contig_us / max(ref_us, 1e-9)), 3
        ),
    }

    # ---- (b) fused unscale+clip+Adam tail over a sharded leaf set.
    keys = jax.random.split(jax.random.PRNGKey(1), 8)
    shapes = {"wte": (1024, 64), "w1": (64, 256), "b1": (256,),
              "w2": (256, 64), "b2": (64,), "ln": (64,)}
    params = {
        n: jax.random.normal(k, s, jnp.float32) * 0.02
        for (n, s), k in zip(shapes.items(), keys)
    }
    grads = {
        n: jax.random.normal(jax.random.fold_in(keys[-1], i), s,
                             jnp.float32)
        for i, (n, s) in enumerate(shapes.items())
    }
    lr, clip, denom = 1e-3, 1.0, 8.0

    def sched(_count):
        return jnp.asarray(lr, jnp.float32)

    tx = optax.chain(optax.identity(), optax.adam(sched))
    opt_state = tx.init(params)
    one = jnp.asarray(1.0, jnp.float32)

    def ref_tail(g, p, st):
        g = jax.tree.map(lambda t: t / denom, g)
        sq = sum(
            jnp.sum(jnp.square(t.astype(jnp.float32)))
            for t in jax.tree.leaves(g)
        )
        factor = clip / jnp.maximum(jnp.sqrt(sq), clip)
        g = jax.tree.map(lambda t: t * factor, g)
        updates, new_st = tx.update(g, st, p)
        updates = jax.tree.map(lambda u: u * one, updates)
        return optax.apply_updates(p, updates), new_st

    def fused_tail(g, p, st):
        _e, (adam_st, sched_st) = st
        g_def = jax.tree.structure(g)
        gs, sq = [], 0.0
        for t in jax.tree.leaves(g):
            th, s = unscale_sqsum(t, denom, compute_sq=True)
            gs.append(th)
            sq = sq + s
        factor = clip / jnp.maximum(jnp.sqrt(sq), clip)
        count_inc, bc1, bc2, step_size, sched_inc = adam_scalars(
            adam_st.count, sched_st.count, sched
        )
        outs = [
            fused_adam_update(t, pv, mu, nu, bc1=bc1, bc2=bc2,
                              step_size=step_size, lr_scale=one,
                              factor=factor)
            for t, pv, mu, nu in zip(
                gs, jax.tree.leaves(p),
                jax.tree.leaves(adam_st.mu), jax.tree.leaves(adam_st.nu),
            )
        ]
        new_p = jax.tree.unflatten(g_def, [o[0] for o in outs])
        new_st = (optax.EmptyState(), (
            optax.ScaleByAdamState(
                count=count_inc,
                mu=jax.tree.unflatten(g_def, [o[1] for o in outs]),
                nu=jax.tree.unflatten(g_def, [o[2] for o in outs]),
            ),
            optax.ScaleByScheduleState(count=sched_inc),
        ))
        return new_p, new_st

    adam_ref_us = best_us(ref_tail, grads, params, opt_state)
    adam_fused_us = best_us(fused_tail, grads, params, opt_state)
    adam_parity = bits_equal(
        jax.jit(ref_tail)(grads, params, opt_state),
        jax.jit(fused_tail)(grads, params, opt_state),
    )
    kernels["fused_adam"] = {
        "n_params": int(sum(np.prod(s) for s in shapes.values())),
        "n_leaves": len(shapes),
        "reference_us": adam_ref_us,
        "kernel_us": adam_fused_us,
        "speedup": round(adam_ref_us / max(adam_fused_us, 1e-9), 3),
        "trajectory_parity": adam_parity,
    }

    # ---- (c) int8 weight-quantized matmul at a decode-like shape.
    m, k, n = 8, 256, 1024
    x = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n), jnp.float32) * 0.1
    w_q, scale = quantize_per_channel(w)
    fp32_us = best_us(lambda a, bm: a @ bm, x, w)
    int8_us = best_us(int8_matmul, x, w_q, scale)
    y_fp = np.asarray(x @ w)
    y_q = np.asarray(int8_matmul(x, w_q, scale))
    int8_parity = bits_equal(
        int8_matmul(x, w_q, scale, implementation="pallas",
                    interpret=True),
        int8_matmul(x, w_q, scale, implementation="reference"),
    )
    kernels["int8_matmul"] = {
        "shape": {"m": m, "k": k, "n": n},
        "reference_us": fp32_us,   # the fp32 Dense this path replaces
        "kernel_us": int8_us,
        "speedup": round(fp32_us / max(int8_us, 1e-9), 3),
        "interpret_parity": int8_parity,
        "max_abs_err": round(float(np.abs(y_fp - y_q).max()), 5),
        "argmax_agreement": round(
            float((y_fp.argmax(-1) == y_q.argmax(-1)).mean()), 4
        ),
    }

    # ---- decode leg: the real engine, gather+flash vs paged_kernel.
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    rng = np.random.default_rng(0)
    prompts = [
        np.asarray(rng.integers(0, 1024, ln), np.int32)
        for ln in (5, 3, 12, 7, 17, 9)
    ]

    def run_requests(paged_kernel):
        outs = []
        with Server(model, variables, max_batch=4, kv_page_size=16,
                    paged_kernel=paged_kernel) as server:
            streams = [
                server.submit(p, new_tokens, temperature=0.7, rng=42)
                if i == 3 else server.submit(p, new_tokens)
                for i, p in enumerate(prompts)
            ]
            for s in streams:
                outs.append(np.asarray(s.result(timeout=600)))
        return outs

    compile_watch.install()
    byte_identical = all(
        np.array_equal(a, bmat)
        for a, bmat in zip(run_requests(False), run_requests(True))
    )

    def decode_step_us(paged_kernel, pin=False):
        eng = SlotDecodeEngine(model, variables, max_batch=4,
                               kv_page_size=16,
                               paged_kernel=paged_kernel)
        cache, tok = eng.cache, eng.tok
        for _ in range(warmup):
            cache, tok = eng._decode(
                eng.params, cache, tok, eng._temps, eng._rngs, eng._steps
            )
        jax.block_until_ready(tok)
        if pin:
            compile_watch.mark_warm()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                cache, tok = eng._decode(
                    eng.params, cache, tok, eng._temps, eng._rngs,
                    eng._steps,
                )
            jax.block_until_ready(tok)
            best = min(best, (time.perf_counter() - t0) / iters)
        return round(best * 1e6, 2)

    gather_step_us = decode_step_us(False)
    kernel_step_us = decode_step_us(True, pin=True)
    post_warmup = compile_watch.post_warmup_count()

    decode = {
        "n_requests": len(prompts),
        "new_tokens": new_tokens,
        "byte_identical": byte_identical,
        "gather_step_us": gather_step_us,
        "kernel_step_us": kernel_step_us,
        "kernel_vs_gather": round(
            gather_step_us / max(kernel_step_us, 1e-9), 3
        ),
        "decode_steps_per_sec": round(1e6 / max(kernel_step_us, 1e-9), 1),
        "post_warmup_compiles": post_warmup,
    }
    for name, row in kernels.items():
        print(
            f"# kernels {name:>16} ref {row['reference_us']:>9,.1f} us  "
            f"fused {row['kernel_us']:>9,.1f} us  "
            f"x{row['speedup']:.2f}", flush=True,
        )
    print(
        f"# kernels decode gather {gather_step_us:,.1f} us/step  kernel "
        f"{kernel_step_us:,.1f} us/step  identical={byte_identical}  "
        f"post-warmup compiles={post_warmup}", flush=True,
    )
    result = {
        "model": "gpt2_tiny(max_len=64)",
        "backend": backend,
        "note": (
            "off-TPU every dispatcher resolves 'auto' to its lax "
            "reference, so reference/kernel columns converge by "
            "construction; parity + byte identity are the gate and the "
            "timing columns ratchet the shared program — regenerate on "
            "a chip for the fused-kernel win"
        ),
        "kernels": kernels,
        "decode": decode,
    }
    if out_path:
        _write_kernels_artifact(result, out_path)
    return result


def _write_kernels_artifact(result, out_path) -> None:
    import os

    payload = dict(result)
    payload["generated_by"] = "bench.py --kernels"
    payload["date"] = _utcnow()
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fp:
        json.dump(payload, fp, indent=1)
    os.replace(tmp, out_path)
    print(f"# kernels artifact -> {out_path}", flush=True)


def bench_extended():
    """North-star table, every row IN THIS PROCESS: a chip belongs to one
    process, so the process that holds it runs all the models one after
    another.  A row that raises is recorded as an error (main() then
    exits non-zero) and the table goes on."""
    out = []
    for name, (shape, _kind, _kw) in EXTENDED_CONFIGS.items():
        try:
            row = bench_one_model(name)
            # The allocator's peak never resets: from the second row on
            # this is the largest of the rows so far, not this model's.
            row["peak_hbm_scope"] = "process, rows so far"
        except Exception as e:
            row = {"model": name, "batch_shape": list(shape),
                   "error": f"FAILED: {type(e).__name__}: {e}",
                   **_device_stamp()}
        out.append(row)
        if "error" in row:
            print(f"# {name} {shape}: {row['error']}")
        else:
            mfu = row.get("mfu")
            mfu_s = f" MFU={mfu * 100:.1f}%" if mfu is not None else ""
            print(
                f"# {name} {shape}: {row['samples_per_sec']:,.1f} "
                f"samples/s{mfu_s} on {row['device_kind']}"
            )
    return out


def bench_memplan(args) -> dict:
    """``--memplan``: the analytic fit-or-OOM planner.  Prices a model ×
    batch × parallelism config per device (telemetry/memory.py formula
    walk — ``jax.eval_shape`` only) and judges the predicted peak
    against the chip HBM capacity table (telemetry/flops.py)."""
    from ml_trainer_tpu.models.registry import get_model
    from ml_trainer_tpu.telemetry import memory as _memory

    mesh_shape = {}
    for part in (args.memplan_mesh or "").split(","):
        if part.strip():
            axis, _, n = part.partition("=")
            mesh_shape[axis.strip()] = int(n)
    name = args.memplan
    model = get_model(
        name, **(EXTENDED_CONFIGS[name][2]() if name in EXTENDED_CONFIGS
                 else {})
    )
    batch = args.batch_size or (
        EXTENDED_CONFIGS[name][0][0] if name in EXTENDED_CONFIGS else 32
    )
    if name in EXTENDED_CONFIGS:
        shape = (batch,) + tuple(EXTENDED_CONFIGS[name][0][1:])
    elif getattr(model, "max_len", 0):
        shape = (batch, args.memplan_seq or int(model.max_len))
    else:
        shape = (batch, 32, 32, 3)
    ledger = _memory.plan_train_memory(
        model, shape,
        optimizer=args.memplan_optimizer,
        mesh_shape=mesh_shape,
        shard_opt_state=args.memplan_zero1,
        precision=args.memplan_precision,
    )
    verdict = _memory.fit_verdict(
        ledger.peak_bytes(), generation=args.memplan_chip
    )
    for c in ledger.components:
        print(f"# {c.name:<18} {c.bytes / 2 ** 20:10.2f} MiB  ({c.kind})",
              file=sys.stderr)
    print(
        f"# peak {ledger.peak_bytes() / 2 ** 30:.2f} GiB vs "
        f"{verdict['chip']} capacity "
        f"{verdict['capacity_bytes'] / 2 ** 30:.0f} GiB -> "
        f"{verdict['verdict'].upper()}",
        file=sys.stderr,
    )
    return {
        "model": name, "batch_shape": list(shape),
        "mesh": mesh_shape or {"data": 1},
        "optimizer": args.memplan_optimizer,
        "zero1": bool(args.memplan_zero1),
        "precision": args.memplan_precision,
        "ledger": ledger.as_dict(),
        "fit": verdict,
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--extended", action="store_true",
                        help="also bench the north-star model zoo")
    parser.add_argument("--one", metavar="MODEL", default=None,
                        choices=sorted(EXTENDED_CONFIGS),
                        help="bench a single north-star model, print one "
                        "JSON line")
    parser.add_argument("--cpu", action="store_true",
                        help="pin the CPU backend: an explicitly labelled "
                        "host run.  Without it the default row, --one and "
                        "--extended fail when no TPU is attached")
    parser.add_argument("--loaders", action="store_true",
                        help="run only the host input-pipeline benchmark "
                        "(Python vs C++ loader; no device work)")
    parser.add_argument("--spec", action="store_true",
                        help="run only the speculative-decoding benchmark: "
                        "n-gram lookup drafting vs the vanilla compiled "
                        "decode loop on a repetitive greedy workload "
                        "(gpt2_tiny; CPU-safe)")
    parser.add_argument("--dispatch", action="store_true",
                        help="run only the pjit dispatch microbenchmark: "
                        "per-call host overhead of the compiled train and "
                        "decode steps (CPU-safe)")
    parser.add_argument("--chaos", action="store_true",
                        help="run only the chaos/recovery benchmark: "
                        "step-checkpoint overhead, steps lost on "
                        "preemption, time-to-recover (MLModel; CPU-safe)")
    parser.add_argument("--telemetry", action="store_true",
                        help="run only the telemetry-overhead benchmark: "
                        "instrumented (Trainer(telemetry=True)) vs bare "
                        "step time on the CPU mlmodel row (target <2%% "
                        "overhead; CPU-safe)")
    parser.add_argument("--serve", action="store_true",
                        help="run only the serving benchmark: the "
                        "continuous-batching engine vs a generate_ragged "
                        "dynamic-batching baseline on ragged Poisson "
                        "arrivals (gpt2_tiny; CPU-safe)")
    parser.add_argument("--serve-replay", action="store_true",
                        help="run only the multi-tenant ragged replay: "
                        "the PAGED engine (page pool + prefix cache + "
                        "tenant scheduler) vs the contiguous engine on an "
                        "80%%-shared-prefix Poisson trace; writes the "
                        "docs/serving_replay_cpu.json artifact "
                        "(gpt2_tiny; CPU-safe)")
    parser.add_argument("--slo", action="store_true",
                        help="run only the open-loop SLO sweep: fixed "
                        "Poisson arrival schedules at >=3 offered rates "
                        "through the real HTTP server, TTFT/TPOT/queue-"
                        "wait/e2e p50+p99 with SLO attainment + burn rate "
                        "per rate, zero recompiles pinned; writes "
                        "docs/serving_slo_cpu.json (gpt2_tiny; CPU-safe)")
    parser.add_argument("--slo-url", default=None, metavar="URL",
                        help="point the --slo sweep's schedules at an "
                        "EXTERNAL target URL (a single replica's front "
                        "end or the disaggregated router's) instead of "
                        "building a local server; no artifact written")
    parser.add_argument("--serve-lora", action="store_true",
                        help="run only the batched-LoRA serving leg: 64 "
                        "concurrent adapters over one gpt2 base, open-"
                        "loop at saturating load vs the single-model "
                        "baseline on the identical schedule; adapter="
                        "None byte identity, mid-run hot-load and zero "
                        "recompiles pinned; writes "
                        "docs/serving_lora_cpu.json (gpt2_tiny; CPU-safe)")
    parser.add_argument("--serve-lora-url", default=None, metavar="URL",
                        help="point the --serve-lora schedule at an "
                        "EXTERNAL target URL (a replica's front end or "
                        "an adapter-pooled router fleet's) instead of "
                        "building a local server; no artifact written")
    parser.add_argument("--serve-disagg", action="store_true",
                        help="run only the disaggregated-vs-colocated "
                        "router comparison: the same recorded 80%%-"
                        "shared-prefix trace open-loop at saturating "
                        "load through a 2-prefill+2-decode router with "
                        "page-granular KV migration vs 4 colocated "
                        "replicas; byte identity + zero recompiles "
                        "pinned; writes docs/serving_disagg_cpu.json "
                        "(gpt2_tiny; CPU-safe)")
    parser.add_argument("--serve-fleet", action="store_true",
                        help="run only the multi-process fleet bench: "
                        "4 worker PROCESSES behind the socket router, "
                        "chunked prefill on a long+short mix vs "
                        "short-only and unchunked fleets, a real "
                        "SIGKILL + autoscaler respawn; byte identity + "
                        "zero per-process recompiles pinned; writes "
                        "docs/serving_fleet_cpu.json "
                        "(gpt2_tiny; CPU-safe)")
    parser.add_argument("--fleet-obs", action="store_true",
                        help="run only the fleet-observability-plane "
                        "bench: a 3-process fleet under the router's "
                        "metrics federation + cross-process tracing + "
                        "incident bundling, measuring scrape/render/"
                        "trace-merge/bundle latency and pinning the "
                        "plane's invariants (labelled worker series, "
                        "idempotent re-scrape, >= 2 causal trace "
                        "lanes, complete bundle, byte identity, zero "
                        "recompiles); writes docs/fleet_obs_cpu.json "
                        "(gpt2_tiny; CPU-safe)")
    parser.add_argument("--watchtower", action="store_true",
                        help="run only the watchtower bench: the "
                        "in-process TSDB + alert engine + dashboard "
                        "measured on a serving-worker-sized registry "
                        "(sample/ingest/eval/query/render per-call ms) "
                        "with the one-eval-window regression-detection, "
                        "ring-bound and dump-roundtrip invariants "
                        "pinned; writes docs/watchtower_cpu.json "
                        "(pure host; CPU-safe)")
    parser.add_argument("--serve-deploy", action="store_true",
                        help="run only the live-rollout bench: train a "
                        "tiny gpt2 in-bench, export it, and deploy the "
                        "export onto a 2-process fleet MID-LOAD (canary "
                        "-> ramp -> promote), then force a canary "
                        "regression through a wedged factory and pin "
                        "the SLO-burn auto-rollback; zero dropped "
                        "streams, byte identity and zero steady-fleet "
                        "recompiles pinned; writes "
                        "docs/serving_deploy_cpu.json "
                        "(gpt2_tiny; CPU-safe)")
    parser.add_argument("--serve-chaos", action="store_true",
                        help="run only the serving-chaos leg: the recorded "
                        "80%%-shared-prefix trace open-loop at saturating "
                        "load through a 2-prefill+2-decode router while "
                        "1-of-4 replicas is killed and another slowed "
                        "mid-run, with vs without the mitigation stack "
                        "(SLO-burn autoscaler + hedged prefills + circuit "
                        "breakers + degradation ladder); attainment >= 2x "
                        "baseline, byte identity, zero recompiles and "
                        "structured failures pinned; writes "
                        "docs/serving_chaos_cpu.json (gpt2_tiny; CPU-safe)")
    parser.add_argument("--mixed", action="store_true",
                        help="run only the mixed-precision / sharded-update "
                        "matrix: {fp32,bf16} x {fused-psum, bucketed "
                        "reduce-scatter + sharded update} step time and "
                        "comm bytes on a virtual pure-DP mesh (the "
                        "dryrun_multichip style; writes "
                        "docs/mixed_precision_cpu.json; CPU-safe)")
    parser.add_argument("--mixed-devices", type=int, default=8,
                        help="virtual device count for --mixed (default 8)")
    parser.add_argument("--pipeline", action="store_true",
                        help="run only the pipeline-schedule matrix: "
                        "gpipe vs 1f1b vs interleaved vs zb step time, "
                        "analytic bubble fractions, per-hop comm bytes "
                        "and serial-fold equality on a virtual stage "
                        "mesh (writes docs/pipeline_schedules_cpu.json; "
                        "CPU-safe)")
    parser.add_argument("--pipeline-devices", type=int, default=4,
                        help="virtual device count for --pipeline "
                        "(default 4)")
    parser.add_argument("--kernels", action="store_true",
                        help="run only the ops/kernels/ Pallas-pass leg: "
                        "per-kernel reference-vs-dispatch microbench "
                        "(paged attention, fused Adam tail, int8 matmul) "
                        "with interpret-mode parity, plus the real-engine "
                        "gather-vs-paged_kernel decode comparison — byte "
                        "identity and zero post-warmup recompiles pinned; "
                        "writes docs/kernels_cpu.json (gpt2_tiny; "
                        "CPU-safe)")
    parser.add_argument("--memplan", metavar="MODEL", default=None,
                        help="fit-or-OOM planner (telemetry/memory.py): "
                        "analytic per-device HBM ledger for MODEL under "
                        "the given knobs, judged against the chip's HBM "
                        "capacity — no state is built, no device memory "
                        "touched (CPU-safe; works for topologies this "
                        "host does not have)")
    parser.add_argument("--memplan-mesh", default="",
                        help="mesh for --memplan as 'data=8' or "
                        "'data=4,tensor=2' (default: single device)")
    parser.add_argument("--memplan-optimizer", default="adamw",
                        help="optimizer whose moments the --memplan "
                        "ledger prices (default adamw)")
    parser.add_argument("--memplan-zero1", action="store_true",
                        help="price ZeRO-1 moment sharding (÷data) in "
                        "--memplan")
    parser.add_argument("--memplan-precision", default=None,
                        help="compute precision for --memplan (e.g. bf16)")
    parser.add_argument("--memplan-seq", type=int, default=None,
                        help="sequence length override for --memplan LM "
                        "models (default: the model's max_len)")
    parser.add_argument("--memplan-chip", default=None, metavar="GEN",
                        help="TPU generation whose HBM the --memplan "
                        "verdict is judged against (a telemetry/flops.py "
                        "table key, e.g. v5e).  Default: the local chip; "
                        "a host without one must name it")
    parser.add_argument("--batch_size", type=int, default=None,
                        help="override the batch size (headline MLModel "
                        "bench defaults to 32; --one rows default to their "
                        "EXTENDED_CONFIGS shape)")
    args = parser.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.memplan:
        print(json.dumps({"memplan": bench_memplan(args)}, indent=1))
        return
    if not args.one:
        args.batch_size = args.batch_size or 32
    enable_compilation_cache()  # after the --cpu pin, which it reads
    if args.one:
        _require_chip(args.cpu)
        print(json.dumps(bench_one_model(args.one, args.batch_size)),
              flush=True)
        return
    if args.loaders:
        # Host-side only: measures the input pipeline, touches no device.
        bench_loaders()
        return
    if args.chaos:
        # Recovery-overhead leg; tiny model, any backend — plus the
        # elastic leg: kill 1 of N simulated hosts mid-run and measure
        # the reshape downtime / steps-lost / time-to-recover the
        # committed docs/elastic_chaos_cpu.json artifact pins.
        print(json.dumps({"chaos": bench_chaos(), "elastic": bench_elastic()}))
        return
    if args.telemetry:
        # Instrumented-vs-bare step time; tiny model, any backend.
        print(json.dumps({"telemetry": bench_telemetry()}))
        return
    if args.serve:
        # Tiny model; meaningful on any backend.  One JSON line for the
        # driver, engine-vs-baseline, like the headline metric.
        print(json.dumps({"serve": bench_serve()}))
        return
    if args.serve_replay:
        # Paged vs contiguous engine on the multi-tenant shared-prefix
        # trace; the artifact is the acceptance evidence for the paged
        # KV subsystem and feeds scripts/bench_gate.py.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "serving_replay_cpu.json",
        )
        result = bench_serve_replay(out_path=out)
        print(json.dumps({"serve_replay": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.slo:
        # Open-loop capacity-vs-SLO sweep through the real HTTP server;
        # the artifact is what scripts/bench_gate.py gate_slo ratchets.
        # --slo-url redirects the same schedules at an external target
        # (router or replica) with client-side truth, no artifact.
        import os as _os

        out = None if args.slo_url else _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "serving_slo_cpu.json",
        )
        result = bench_slo(out_path=out, target_url=args.slo_url)
        print(json.dumps({"slo": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.serve_lora or args.serve_lora_url:
        # 64 concurrent LoRA adapters over one base vs the single-model
        # baseline; the artifact is the acceptance evidence for the
        # batched-adapter subsystem and feeds bench_gate.py gate_lora.
        # --serve-lora-url redirects the schedule at an external target
        # (e.g. a router fleet with adapter pools), client-side truth.
        import os as _os

        out = None if args.serve_lora_url else _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "serving_lora_cpu.json",
        )
        result = bench_serve_lora(
            out_path=out, target_url=args.serve_lora_url
        )
        print(json.dumps({"serve_lora": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.serve_disagg:
        # Disaggregated vs colocated router at equal replica count; the
        # artifact is the acceptance evidence for the router subsystem
        # and feeds scripts/bench_gate.py gate_disagg.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "serving_disagg_cpu.json",
        )
        result = bench_serve_disagg(out_path=out)
        print(json.dumps({"serve_disagg": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.serve_fleet:
        # True multi-process fleet: socket-only router, chunked
        # prefill, SIGKILL survival; the artifact is the acceptance
        # evidence for serving/fleet.py and feeds bench_gate.py
        # gate_fleet.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "serving_fleet_cpu.json",
        )
        result = bench_serve_fleet(out_path=out)
        print(json.dumps({"serve_fleet": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.fleet_obs:
        # Fleet observability plane: federation + tracing + bundles on
        # a real 3-process fleet; the artifact is the acceptance
        # evidence for the plane's overhead and feeds bench_gate.py
        # gate_fleet.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "fleet_obs_cpu.json",
        )
        result = bench_fleet_obs(out_path=out)
        print(json.dumps({"fleet_obs": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.watchtower:
        # Watchtower TSDB + alert engine + dashboard overhead; the
        # artifact is the acceptance evidence for the fourth
        # observability pillar and feeds bench_gate.py gate_watchtower.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "watchtower_cpu.json",
        )
        result = bench_watchtower(out_path=out)
        print(json.dumps({"watchtower": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.serve_deploy:
        # Live base-model rollout under traffic: canary + auto-rollback
        # on a real multi-process fleet; the artifact is the acceptance
        # evidence for serving/deploy.py and feeds bench_gate.py
        # gate_deploy.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "serving_deploy_cpu.json",
        )
        result = bench_serve_deploy(out_path=out)
        print(json.dumps({"serve_deploy": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.serve_chaos:
        # Serving fleet under chaos (kill + slow) with vs without the
        # mitigation stack; the artifact is the acceptance evidence for
        # the overload subsystem and feeds bench_gate.py gate_overload.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "serving_chaos_cpu.json",
        )
        result = bench_serve_chaos(out_path=out)
        print(json.dumps({"serve_chaos": result}))
        if result.get("error"):
            sys.exit(1)
        return
    if args.mixed:
        # Mixed-precision / sharded-update matrix on virtual devices.
        # The respawned child (env marker) must not write the artifact —
        # its parent does, after validating the child's JSON.
        import os as _os

        child = _os.environ.get("ML_TRAINER_TPU_MIXED_CHILD") == "1"
        out = None if child else _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "mixed_precision_cpu.json",
        )
        result = bench_mixed(n_devices=args.mixed_devices, out_path=out)
        print(json.dumps({"mixed": result}), flush=True)
        if result.get("error"):
            sys.exit(1)
        return
    if args.pipeline:
        # Pipeline-schedule matrix on virtual stage devices.  Like
        # --mixed, the respawned child (env marker) must not write the
        # artifact — its parent does, after validating the child's JSON.
        import os as _os

        child = _os.environ.get("ML_TRAINER_TPU_PIPELINE_CHILD") == "1"
        out = None if child else _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "pipeline_schedules_cpu.json",
        )
        result = bench_pipeline(
            n_devices=args.pipeline_devices, out_path=out
        )
        print(json.dumps({"pipeline": result}), flush=True)
        if result.get("error"):
            sys.exit(1)
        return
    if args.kernels:
        # Kernel-pass microbench + engine decode comparison; the
        # artifact is the acceptance evidence for ops/kernels/ and
        # feeds scripts/bench_gate.py gate_kernels.
        import os as _os

        out = _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)),
            "docs", "kernels_cpu.json",
        )
        result = bench_kernels(out_path=out)
        print(json.dumps({"kernels": result}), flush=True)
        if result.get("error"):
            sys.exit(1)
        return
    if args.spec:
        # Speculative vs vanilla decode; tiny model, any backend.
        print(json.dumps({"spec": bench_spec()}))
        return
    if args.dispatch:
        # Host dispatch overhead canary; touches a trivial program only.
        print(json.dumps({"dispatch": bench_dispatch()}))
        return
    _require_chip(args.cpu)
    record = {
        "metric": (
            f"train_samples_per_sec (MLModel/CIFAR-10, bs={args.batch_size}, "
            "full train step)"
        ),
        "value": None,
        "unit": "samples/s",
        "vs_baseline": None,
        **_device_stamp(),
    }
    try:
        if args.extended:
            bench_loaders()
            record["extended"] = bench_extended()
            failed = [r["model"] for r in record["extended"] if "error" in r]
            if failed:
                record["error"] = f"extended rows failed: {failed}"
        samples_per_sec = bench_parity(args.batch_size)
        record["value"] = round(samples_per_sec, 1)
        record["vs_baseline"] = round(
            samples_per_sec / BASELINE_SAMPLES_PER_SEC, 2
        )
    except Exception as e:
        # The caller gets a parseable JSON line (and a non-zero exit)
        # even on failure.
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))
    if "error" in record:
        sys.exit(1)


if __name__ == "__main__":
    main()
