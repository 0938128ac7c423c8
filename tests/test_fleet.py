"""Multi-process serving fleet (ml_trainer_tpu/serving/fleet.py).

Ground truth is ``generate()``, as everywhere in the serving stack: a
request whose prefill is CHUNKED (windowed across engine-loop
iterations so decode ticks and short admissions interleave), or whose
KV cache crosses a process boundary as serialized bytes over
``POST /v1/adopt``, must still reproduce the standalone batch-1
``generate()`` output byte-for-byte — greedy and seeded-sampling
alike.  The full 4-process fleet (spawned workers, real SIGKILL,
autoscaler respawn) lives in scripts/fleet_smoke.py; these tests pin
the underlying mechanics with in-process servers (the socket tests
still cross a real HTTP socket — the servers just live in this process
behind ``serve_http``).
"""

import os

import jax
import numpy as np
import pytest

from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import Router, Server
from ml_trainer_tpu.serving.fleet import RemoteServer


@pytest.fixture(scope="module")
def model_and_vars():
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    return model, variables


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 1024, n).astype(
        np.int32
    )


# -- chunked prefill ------------------------------------------------------

def test_chunked_prefill_byte_identity_greedy_and_seeded(model_and_vars):
    """Prompts split into page-aligned windows must land EXACTLY where
    a monolithic prefill would: same KV, same sampler state, same
    tokens — including a seeded sampling stream (the per-request PRNG
    key must survive the deferred first token)."""
    model, variables = model_and_vars
    with Server(model, variables, max_batch=2, kv_page_size=8,
                prefill_chunk=16) as server:
        # 40 and 33 span 3 windows (the last one ragged); 9 rides a
        # single sub-window prefill.
        for n, seed in ((40, 0), (33, 1), (9, 2)):
            p = _prompt(n, seed)
            ref = np.asarray(generate(model, variables, p[None], 12))[0]
            out = np.asarray(server.complete(p, 12, timeout=120))
            np.testing.assert_array_equal(out, ref)
        p = _prompt(40, 3)
        ref = np.asarray(
            generate(model, variables, p[None], 10, temperature=0.7,
                     rng=jax.random.PRNGKey(11))
        )[0]
        out = np.asarray(
            server.complete(p, 10, temperature=0.7, rng=11, timeout=120)
        )
        np.testing.assert_array_equal(out, ref)
        snap = server.metrics.snapshot()
        assert snap["chunked_admissions_total"] >= 3
        assert snap["prefill_chunks_total"] >= 6


def test_chunked_prefill_unblocks_short_ttft(model_and_vars):
    """The adversarial long+short pair: with chunking, a short request
    submitted behind a long prompt gets its first token BEFORE the
    long one (it admits and prefills between the long prompt's
    windows); without chunking the monolithic long prefill
    head-of-line-blocks it, so the long request's first token lands
    first.  Both slots are plugged while the pair enqueues (the pair is
    QUEUED together, so the ordering reflects the engine's admission
    interleave, not client-thread timing) and first-token order is read
    from the engine's own ``first_token_at`` stamps — deterministic,
    not a wall-clock threshold."""
    model, variables = model_and_vars
    long_p, short_p = _prompt(48, 4), _prompt(8, 5)
    ref_long = np.asarray(generate(model, variables, long_p[None], 8))[0]
    ref_short = np.asarray(
        generate(model, variables, short_p[None], 8)
    )[0]

    def first_token_order(chunk):
        # prefix_cache off: the warmups below would otherwise turn the
        # timed long prompt into a full prefix hit whose tiny remainder
        # never chunks.
        with Server(model, variables, max_batch=2, kv_page_size=8,
                    prefill_chunk=chunk, prefix_cache=False) as server:
            # Warm both shapes so compile time doesn't serialize the
            # timed pair.
            server.complete(long_p, 2, timeout=120)
            server.complete(short_p, 2, timeout=120)
            plugs = [
                server.submit(_prompt(8, 50 + i), 16) for i in range(2)
            ]
            s_long = server.submit(long_p, 8)
            s_short = server.submit(short_p, 8)
            for s in plugs:
                s.result(timeout=60)
            np.testing.assert_array_equal(
                np.asarray(s_long.result(timeout=60)), ref_long
            )
            np.testing.assert_array_equal(
                np.asarray(s_short.result(timeout=60)), ref_short
            )
            return (s_long.request.first_token_at,
                    s_short.request.first_token_at)

    # chunk=8 -> the 48-token prompt is 6 windows; the short request
    # admits and monolithic-prefills between them.
    t_long, t_short = first_token_order(chunk=8)
    assert t_short < t_long, (
        f"chunked: short first token at {t_short} not ahead of long "
        f"at {t_long}"
    )
    t_long, t_short = first_token_order(chunk=0)
    assert t_long < t_short, (
        f"unchunked: long prefill should head-of-line-block the short "
        f"request (long at {t_long}, short at {t_short})"
    )


def test_prefill_chunk_validation(model_and_vars):
    model, variables = model_and_vars
    with pytest.raises(ValueError, match="prefill_chunk"):
        Server(model, variables, max_batch=2, prefill_chunk=16)  # contig
    with pytest.raises(ValueError, match="multiple"):
        Server(model, variables, max_batch=2, kv_page_size=8,
               prefill_chunk=12)


# -- socket adopt() round trip -------------------------------------------

def test_socket_adopt_round_trip_bit_exact(model_and_vars):
    """Disaggregated prefill->decode where the KV migration crosses a
    REAL HTTP socket: the router drives two servers through
    ``RemoteServer`` proxies (NDJSON streams, ``POST /v1/adopt``
    carrying the serialized export, CRC verified at the receiving
    process) and the continuation must be bit-exact — greedy and
    seeded."""
    model, variables = model_and_vars
    servers, remotes = {}, {}
    router = None
    try:
        for name, role in (("prefill0", "prefill"), ("decode0", "decode")):
            srv = Server(model, variables, max_batch=2, kv_page_size=8,
                         role=role, prefill_chunk=16)
            host, port = srv.serve_http(port=0)
            servers[name] = srv
            remotes[name] = RemoteServer(
                f"http://{host}:{port}", name=name
            )
        assert all(r.pid == os.getpid() for r in remotes.values())
        assert remotes["prefill0"].role == "prefill"
        router = Router(
            dict(remotes),
            replica_urls={n: r.url for n, r in remotes.items()},
            hedging=False,
        )
        for n, seed in ((40, 6), (12, 7)):
            p = _prompt(n, seed)
            ref = np.asarray(generate(model, variables, p[None], 12))[0]
            out = np.asarray(router.complete(p, 12, timeout=120))
            np.testing.assert_array_equal(out, ref)
        p = _prompt(24, 8)
        ref = np.asarray(
            generate(model, variables, p[None], 10, temperature=0.7,
                     rng=jax.random.PRNGKey(3))
        )[0]
        out = np.asarray(
            router.complete(p, 10, temperature=0.7, rng=3, timeout=120)
        )
        np.testing.assert_array_equal(out, ref)
        snap = router.snapshot()
        assert snap["migrations_total"] >= 3
        assert snap["kv_migrated_bytes_total"] > 0
        # The adopt hop really ran: the decode server (which never saw
        # a client submit) produced decode steps.
        assert servers["decode0"].metrics.snapshot()[
            "decode_steps_total"
        ] > 0
    finally:
        if router is not None:
            router.close()
        for srv in servers.values():
            srv.close()
