"""One decode step of lookahead (``SlotDecodeEngine.advance``, the serving
loop's call): step n+1 is dispatched before step n's tokens are read and
delivered.  The same work in another order, so everything a client sees is
what the synchronous ``engine.step()`` and ``generate()`` give, byte for
byte; what differs is told by ``ahead``, ``dropped`` and two totals.

The loop is driven from the test's own thread, one iteration at a time
(``Stepwise``), so that which step a request joins at, expires at or is
cancelled at is the test's choice and not the scheduler's.
"""

import time

import jax
import numpy as np
import pytest

from ml_trainer_tpu.generate import generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.serving import DeadlineExceeded, Server
from ml_trainer_tpu.serving.metrics import ServingMetrics
from ml_trainer_tpu.telemetry.spans import clear_trace, trace_events

PS = 8  # page size of the paged engines here


@pytest.fixture(scope="module")
def tiny():
    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    return model, variables


@pytest.fixture(scope="module")
def routed():
    """A model with ``step_counter_args``: its decode step returns
    counters beside the tokens (top 2 of 16 experts, 4 held)."""
    model = get_model("exaone_moe_tiny", experts_held=(0, 4))
    variables = model.init(
        {"params": jax.random.PRNGKey(3)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    return model, variables


def _prompt(seed, n, vocab=1024):
    return np.asarray(
        np.random.default_rng(seed).integers(0, vocab, n), np.int32
    )


class Stepwise:
    """A ``Server`` whose loop the test turns by hand.  ``synchronous``
    puts ``engine.step()`` where the loop calls ``engine.advance()``:
    the order every engine had before the lookahead."""

    def __init__(self, model, variables, synchronous=False, **options):
        self.server = Server(model, variables, watchdog_timeout=None,
                             **options)
        self.server.close()              # its own thread ends; ours turns it
        self.server._stopping = False
        self.engine, self.submit = self.server.engine, self.server.submit
        if synchronous:
            self.engine.advance = self.engine.step

    def turn(self, iterations=1):
        srv = self.server
        left = iter(range(iterations - 1, -1, -1))

        def last_one_stops():
            if next(left) == 0:
                srv._stopping = True

        srv._fault_hooks = last_one_stops
        srv._stopping = False
        srv._loop_inner()
        srv._stopping = False

    def until_done(self, streams, limit=400):
        for _ in range(limit):
            if all(s.request.finished_at is not None for s in streams):
                # The step still in flight carries rows nobody waits for.
                self.turn()
                assert not self.engine.in_flight()
                return
            self.turn()
        raise AssertionError("the requests did not finish")


def _delivered_once(stream):
    """What the request's queue holds: every token once, in order, and one
    end.  Drains it, so ``result()`` afterwards only reads the state."""
    from ml_trainer_tpu.serving.scheduler import _DONE

    q, items = stream.request._stream, []
    while not q.empty():
        items.append(q.get_nowait())
    assert items == list(stream.request.tokens) + [_DONE]
    stream._drained = True


def _events(name):
    return [e for e in trace_events() if e["ph"] == "X" and e["name"] == name]


# -- equivalence -----------------------------------------------------------

# A case: the model fixture, the engine's slots, and the requests as
# (turn it is submitted at, prompt, budget, submit options); ``at`` and
# ``what`` say what is done to request 0 after that many turns.
CASES = {
    "greedy-join-and-leave": dict(
        slots=2, requests=[(0, (1, 5), 14, {}), (2, (2, 3), 5, {}),
                           (3, (3, 7), 6, {}), (9, (4, 4), 3, {})]),
    "sampled-with-seeds": dict(
        slots=3, requests=[
            (0, (5, 6), 10, dict(temperature=0.7, rng=42)),
            (1, (6, 4), 8, dict(temperature=1.3, rng=7)),
            (3, (7, 5), 9, dict(temperature=0.9)),     # rng-less: key 0
            (4, (8, 3), 6, {})]),
    "eos-while-the-next-step-flies": dict(
        slots=2, eos_at=4, requests=[(0, (9, 5), 12, {}),
                                     (1, (10, 4), 9, {})]),
    "budget-of-one": dict(
        slots=2, requests=[(0, (11, 5), 1, {}), (0, (12, 4), 6, {})]),
    "budget-of-two": dict(
        slots=2, requests=[(0, (13, 5), 2, {}), (1, (14, 4), 2, {}),
                           (2, (15, 6), 5, {})]),
    "deadline-mid-decode": dict(
        slots=2, at=4, what="expire",
        requests=[(0, (16, 5), 20, {}), (1, (17, 4), 9, {})]),
    "cancel-mid-decode": dict(
        slots=2, at=5, what="cancel",
        requests=[(0, (18, 5), 20, {}), (2, (19, 4), 9, {})]),
    "counters-beside-the-tokens": dict(
        model="routed", slots=3,
        requests=[(0, (20, 9), 12, {}), (2, (21, 11), 4, {}),
                  (3, (22, 5), 7, {})]),
}


def _serve(case, model, variables, synchronous, eos):
    """Turn the loop through the case's script; returns the streams, the
    server's metrics and this run's spans."""
    vocab = int(model.vocab_size)
    clear_trace()
    sw = Stepwise(model, variables, synchronous=synchronous,
                  max_batch=case["slots"])
    streams, script = [], sorted(case["requests"], key=lambda r: r[0])
    turn = 0
    while script or turn <= case.get("at", -1):
        while script and script[0][0] <= turn:
            _, (seed, n), budget, options = script.pop(0)
            streams.append(sw.submit(
                _prompt(seed, n, vocab), budget,
                eos_token_id=eos if not streams else None, **options))
        if turn == case.get("at", -1):
            first = streams[0].request
            if case["what"] == "expire":
                first.deadline = 1e-9        # the next landing finds it late
            else:
                sw.server.cancel(first)
        sw.turn()
        turn += 1
    sw.until_done(streams)
    spans = {name: _events(name) for name in (
        "serve_decode", "serve_decode.dispatch", "serve_decode.fence",
        "serve_deliver")}
    return streams, sw.server.metrics.snapshot(), spans


@pytest.mark.parametrize("name", list(CASES))
def test_lookahead_serves_what_step_and_generate_serve(name, request):
    case = CASES[name]
    model, variables = request.getfixturevalue(case.get("model", "tiny"))
    vocab = int(model.vocab_size)
    refs = []
    for _, (seed, n), budget, options in sorted(
            case["requests"], key=lambda r: r[0]):
        rng = options.get("rng")
        refs.append(np.asarray(generate(
            model, variables, _prompt(seed, n, vocab)[None], budget,
            temperature=options.get("temperature", 0.0),
            rng=None if rng is None else jax.random.PRNGKey(rng)))[0])
    eos = None
    if "eos_at" in case:
        # A token request 0 first emits at index ``eos_at`` of its reply:
        # it ends there, on a decode step, with the next one in flight.
        n0 = case["requests"][0][1][1]
        reply = refs[0][n0:]
        eos = int(reply[case["eos_at"]])
        assert eos not in reply[:case["eos_at"]]
        refs[0] = refs[0][:n0 + case["eos_at"] + 1]

    ahead, ahead_snap, ahead_spans = _serve(
        case, model, variables, False, eos)
    sync, sync_snap, _ = _serve(case, model, variables, True, eos)

    cut = "what" in case
    for i, (a, s, ref) in enumerate(zip(ahead, sync, refs)):
        got_a = np.concatenate([a._prompt, a.request.tokens])
        got_s = np.concatenate([s._prompt, s.request.tokens])
        if cut and i == 0:
            # Cut short at a turn of the test's choosing: what arrived is
            # generate()'s, as far as it goes; the landing is one step
            # behind, so one token fewer than the synchronous order.
            assert a.request.state == s.request.state == (
                "expired" if case["what"] == "expire" else "error")
            np.testing.assert_array_equal(got_a, ref[:len(got_a)])
            np.testing.assert_array_equal(got_s, ref[:len(got_s)])
            assert len(got_s) == len(got_a) + 1 < len(ref)
            _delivered_once(a)
            with pytest.raises(
                    DeadlineExceeded if case["what"] == "expire"
                    else RuntimeError):
                a.result(timeout=5)
            continue
        assert a.request.state == s.request.state == "done"
        _delivered_once(a)
        np.testing.assert_array_equal(a.result(timeout=5), ref)
        np.testing.assert_array_equal(got_s, ref)

    # The synchronous order never has a step in flight; the loop's has one
    # for every step but the first after a landing.
    assert sync_snap["decode_steps_ahead_total"] == 0
    assert sync_snap["decode_rows_dropped_total"] == 0
    assert ahead_snap["engine_errors"] == sync_snap["engine_errors"] == 0
    steps = ahead_snap["decode_steps_total"]
    if name == "budget-of-one":
        assert len(ahead[0].request.tokens) == 1
    assert 0 < ahead_snap["decode_steps_ahead_total"] < steps
    # A request that ends on a decode step leaves one row behind it, in
    # the step dispatched before its last token was read.
    assert ahead_snap["decode_rows_dropped_total"] == sum(
        e["args"]["dropped"] for e in ahead_spans["serve_deliver"])
    ended_on_a_step = sum(
        1 for a in ahead if len(a.request.tokens) > 1 or cut)
    assert 1 <= ahead_snap["decode_rows_dropped_total"] <= ended_on_a_step

    if case.get("model") == "routed":
        # The counters of step k are printed on the fence of step k: its
        # routed rows are the rows in flight at ITS dispatch, which the
        # turn that dispatched it names, times the experts a token.
        riders = {e["args"]["engine_step"]: e["args"]["active"]
                  for e in ahead_spans["serve_decode"]
                  if e["args"]["engine_step"] in {
                      d["args"]["engine_step"]
                      for d in ahead_spans["serve_decode.dispatch"]}}
        fences = ahead_spans["serve_decode.fence"]
        assert len(fences) == steps and len(set(riders.values())) > 1
        for f in fences:
            assert f["args"]["routed_rows"] == 2 * riders[
                f["args"]["engine_step"]]
            assert 0 <= f["args"]["expert_rows"] <= f["args"]["routed_rows"]


# -- ordering --------------------------------------------------------------


def _dispatch_by_hand(engine):
    """A step in flight where the engine itself would never leave one (a
    paged engine lands every step in the turn that dispatched it)."""
    assert engine._prepare(False) == ([], True)
    engine._dispatch()


def _flying(sw, streams):
    """Turn until a step is in flight with every stream decoding."""
    for _ in range(50):
        sw.turn()
        if sw.engine.in_flight() and all(
                s.request.tokens for s in streams):
            return
    raise AssertionError("no step in flight")


def test_an_admission_finds_the_step_in_flight_landed(tiny):
    model, variables = tiny
    pA, pB = _prompt(30, 5), _prompt(31, 6)
    sw = Stepwise(model, variables, max_batch=2)
    a = sw.submit(pA, 16)
    _flying(sw, [a])
    seen = []
    real_admit, real_land = sw.engine.admit, sw.engine.land

    def land():
        seen.append(("land", sw.engine.in_flight()))
        return real_land()

    def admit(req, slot):
        seen.append(("admit", sw.engine.in_flight()))
        return real_admit(req, slot)

    sw.engine.land, sw.engine.admit = land, admit
    before = len(a.request.tokens)
    b = sw.submit(pB, 5)
    sw.turn()
    # Landed (a step WAS in flight), then admitted with none in flight;
    # the landing delivered its token before the prefill began.
    assert seen[:2] == [("land", True), ("admit", False)]
    assert len(a.request.tokens) >= before + 1
    sw.until_done([a, b])
    np.testing.assert_array_equal(
        a.result(timeout=5),
        np.asarray(generate(model, variables, pA[None], 16))[0])
    np.testing.assert_array_equal(
        b.result(timeout=5),
        np.asarray(generate(model, variables, pB[None], 5))[0])


def test_a_slot_the_landing_frees_is_filled_an_iteration_later(tiny):
    """Two requests end on consecutive steps with two more waiting.  The
    landing that precedes the first admission frees the second slot; filled
    in the same iteration it would put two prefills into one gap between
    the others' tokens.  Each admission has an iteration, and a decode
    step, of its own: the cadence of the synchronous order."""
    model, variables = tiny
    prompts = [_prompt(50 + i, 4 + i) for i in range(4)]
    budgets = [4, 5, 6, 6]
    admitted_in = {True: [], False: []}
    for synchronous in (True, False):
        sw = Stepwise(model, variables, synchronous=synchronous,
                      max_batch=2)
        streams = [sw.submit(p, n) for p, n in zip(prompts, budgets)]
        real_admit, turn = sw.engine.admit, [0]

        def admit(req, slot, real_admit=real_admit, turn=turn,
                  log=admitted_in[synchronous]):
            log.append(turn[0])
            return real_admit(req, slot)

        sw.engine.admit = admit
        for turn[0] in range(60):
            sw.turn()
        for stream, p, n in zip(streams, prompts, budgets):
            np.testing.assert_array_equal(
                stream.result(timeout=5),
                np.asarray(generate(model, variables, p[None], n))[0])
    for log in admitted_in.values():
        assert log[0] == log[1] == 0             # both slots free at first
        assert log[3] == log[2] + 1              # then one an iteration
    # The landing is one step behind, so is the slot it frees.
    assert admitted_in[False][2] == admitted_in[True][2] + 1


def test_a_slot_given_back_at_once_is_refilled_in_the_same_iteration(tiny):
    """An admission that ends on its first token gives its slot back, and
    the loop fills it again before the next decode step, as the synchronous
    order does: only the slots that the landing freed wait."""
    model, variables = tiny
    pA = _prompt(60, 5)
    shorts = [_prompt(61 + i, 4 + i) for i in range(3)]
    for synchronous in (True, False):
        sw = Stepwise(model, variables, synchronous=synchronous,
                      max_batch=2)
        a = sw.submit(pA, 12)
        sw.turn(3)
        assert sw.engine.in_flight() != synchronous
        streams = [sw.submit(p, 1) for p in shorts]
        real_admit, admitted = sw.engine.admit, []

        def admit(req, slot, real_admit=real_admit, admitted=admitted):
            admitted.append(req)
            return real_admit(req, slot)

        sw.engine.admit = admit
        sw.turn()
        assert admitted == [s.request for s in streams]
        assert all(s.request.state == "done" for s in streams)
        sw.until_done([a])
        for stream, p, n in zip([a] + streams, [pA] + shorts, [12, 1, 1, 1]):
            np.testing.assert_array_equal(
                stream.result(timeout=5),
                np.asarray(generate(model, variables, p[None], n))[0])


@pytest.mark.parametrize("what", [
    "admit", "advance_chunks", "export_slot", "import_slot"])
def test_the_engine_refuses_what_is_not_a_step_with_one_in_flight(
        tiny, what):
    """The loop lands first; an engine asked out of order says so instead
    of reading a token buffer the next step owns."""
    from ml_trainer_tpu.serving.scheduler import Request

    model, variables = tiny
    sw = Stepwise(model, variables, max_batch=2, kv_page_size=PS,
                  prefill_chunk=PS)
    engine = sw.engine
    a = Request(prompt=_prompt(32, 5), max_new_tokens=12)
    assert engine.admit(a, 0) == "active"
    long = Request(prompt=_prompt(33, 3 * PS + 2), max_new_tokens=4)
    if what == "advance_chunks":
        assert engine.admit(long, 1) == "chunking"
    _dispatch_by_hand(engine)
    assert engine.in_flight()
    call = {
        "admit": lambda: engine.admit(long, 1),
        "advance_chunks": engine.advance_chunks,
        "export_slot": lambda: engine.export_slot(0),
        "import_slot": lambda: engine.import_slot(long, 1, None),
    }[what]
    with pytest.raises(RuntimeError, match="decode step in flight"):
        call()
    assert engine.land() == [] and not engine.in_flight()
    assert len(a.tokens) == 2
    if what != "import_slot":
        call()                           # landed: served as ever


def test_a_chunk_window_and_an_export_find_the_step_landed(tiny):
    """A paged engine never looks ahead, so the step in flight is put
    there by hand: the loop's chunk advance, and its export of a request
    that a migration sink waits for, land it first."""
    model, variables = tiny
    pA, pL, pM = _prompt(34, 5), _prompt(35, 3 * PS + 2), _prompt(36, 6)
    sw = Stepwise(model, variables, max_batch=3, kv_page_size=PS,
                  prefill_chunk=PS)
    a = sw.submit(pA, 12)
    long = sw.submit(pL, 4)
    sw.turn()
    assert sw.engine.chunking_count() == 1 and a.request.tokens
    _dispatch_by_hand(sw.engine)
    assert sw.engine.in_flight()
    sw.turn()                            # no admission: the chunk advance lands
    assert sw.server.metrics.snapshot()["engine_errors"] == 0

    shipped = []
    from ml_trainer_tpu.serving.scheduler import Request

    moving = Request(prompt=pM, max_new_tokens=5)
    moving.migration_sink = lambda req, export: shipped.append(
        (req, export))
    _dispatch_by_hand(sw.engine)
    sw.server.submit_request(moving)
    sw.turn()
    sw.until_done([a, long])
    assert sw.server.metrics.snapshot()["engine_errors"] == 0
    assert len(shipped) == 1 and shipped[0][1].step_counter == 1
    np.testing.assert_array_equal(
        a.result(timeout=5),
        np.asarray(generate(model, variables, pA[None], 12))[0])
    np.testing.assert_array_equal(
        long.result(timeout=5),
        np.asarray(generate(model, variables, pL[None], 4))[0])


def test_close_lands_the_step_in_flight_and_no_stream_hangs(tiny):
    model, variables = tiny
    pA = _prompt(37, 5)
    ref = np.asarray(generate(model, variables, pA[None], 40))[0]
    server = Server(model, variables, max_batch=2)
    a = server.submit(pA, 40)
    it = iter(a)
    for _ in range(3):
        next(it)
    server.close()
    assert not server.engine.in_flight()
    with pytest.raises(RuntimeError, match="server closed"):
        a.result(timeout=10)             # ended, not hung
    got = np.asarray(a.request.tokens)
    np.testing.assert_array_equal(got, ref[5:5 + len(got)])
    # Every step dispatched was landed: as many samples as dispatches.
    assert (server.metrics.snapshot()["decode_steps_total"]
            == server.engine._step_seq)


def test_an_engine_error_fails_the_riders_once_and_delivers_nothing_more(
        tiny):
    model, variables = tiny
    pA, pB, pC = _prompt(38, 5), _prompt(39, 4), _prompt(40, 6)
    sw = Stepwise(model, variables, max_batch=2)
    a, b = sw.submit(pA, 20), sw.submit(pB, 20)
    _flying(sw, [a, b])
    real = sw.engine._dispatch

    def refused():
        sw.engine._dispatch = real
        raise RuntimeError("Mosaic refused the decode step")

    sw.engine._dispatch = refused
    had = [len(a.request.tokens), len(b.request.tokens)]
    sw.turn()
    assert not sw.engine.in_flight() and sw.engine.active_count() == 0
    for s, n in zip((a, b), had):
        assert s.request.state == "error"
        assert len(s.request.tokens) == n    # the step in flight: abandoned
        _delivered_once(s)                   # one end, no hang
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            s.result(timeout=5)
    assert sw.server.metrics.snapshot()["engine_errors"] == 1
    c = sw.submit(pC, 7)                 # the loop serves on
    sw.until_done([c])
    np.testing.assert_array_equal(
        c.result(timeout=5),
        np.asarray(generate(model, variables, pC[None], 7))[0])


@pytest.mark.parametrize("what", [
    "admission-error", "adoption-error", "admission-wedge"])
def test_a_landing_that_fails_takes_the_request_it_was_made_for(tiny, what):
    """The landing before an admission or an adoption is a device fence.
    The request it is made for has left the queue and is not among the
    engine's active ones yet: a fence that raises there, or one that the
    watchdog finds wedged, fails it with the riders, once, and its slot and
    its tenant's count come back."""
    from ml_trainer_tpu.serving.scheduler import _DONE, Request

    model, variables = tiny
    pA, pB, pC = _prompt(70, 5), _prompt(71, 4), _prompt(72, 6)
    adoption = what == "adoption-error"
    # Only a paged engine adopts, and it never looks ahead: by hand then.
    sw = Stepwise(model, variables, max_batch=2,
                  **(dict(kv_page_size=PS) if adoption else {}))
    a = sw.submit(pA, 20)
    if adoption:
        sw.turn(2)
        _dispatch_by_hand(sw.engine)
    else:
        _flying(sw, [a])
    verdicts, at_the_wedge = [], []
    if adoption:
        req = Request(prompt=pB, max_new_tokens=6)
        sw.server.adopt(req, None,
                        resolver=lambda s, d: verdicts.append((s, d)))
    else:
        req = sw.submit(pB, 6).request
    real = sw.engine._fence

    def fence(landing):
        sw.engine._fence = real
        assert sw.server._admitting_req is req and req.state == "active"
        if what == "admission-wedge":
            # What the watchdog does from its thread while this one hangs.
            sw.server._mark_unhealthy("decode engine wedged")
            at_the_wedge.append((req.state, a.request.state))
        raise RuntimeError("device lost in the fence")

    sw.engine._fence = fence
    had = len(a.request.tokens)
    sw.turn()
    assert sw.engine._fence is real          # the landing was reached
    assert sw.server._admitting_req is None
    assert not sw.engine.in_flight() and sw.engine.active_count() == 0
    for r in (a.request, req):
        assert r.state == "error" and r.finished_at is not None
    _delivered_once(a)
    assert len(a.request.tokens) == had      # nothing follows the error
    ends = []
    while not req._stream.empty():
        ends.append(req._stream.get_nowait())
    assert ends == [_DONE]                   # failed once, no token
    if what == "admission-wedge":
        # Failed by the watchdog, before the thread came back.
        assert at_the_wedge == [("error", "error")]
        assert "wedged" in req.error and not sw.server.healthy
        return
    assert "device lost in the fence" in req.error
    assert verdicts == (
        [("error", "RuntimeError: device lost in the fence")]
        if adoption else [])
    assert sw.server.metrics.snapshot()["engine_errors"] == 1
    assert sw.engine.free_capacity() == 2    # both slots are back,
    assert len(sw.server.scheduler._free_slots) == 2
    assert sw.server.scheduler.active_counts() == {}
    c = sw.submit(pC, 7)                     # and the loop serves on
    sw.until_done([c])
    np.testing.assert_array_equal(
        c.result(timeout=5),
        np.asarray(generate(model, variables, pC[None], 7))[0])


# -- accounting ------------------------------------------------------------


def _when_all_landed(server):
    """The snapshot once the loop's thread has landed its last step (a
    reply returns to its client before that step's sample is recorded):
    as many samples as steps dispatched."""
    deadline = time.monotonic() + 30
    while True:
        snap = server.metrics.snapshot()
        if (snap["decode_steps_total"] == server.engine._step_seq
                and not server.engine.in_flight()):
            return snap
        assert time.monotonic() < deadline, snap
        time.sleep(0.01)


def test_ahead_and_dropped_count_what_happened(tiny):
    """One request of N tokens alone: its first token is the prefill's,
    N - 1 come from decode steps, and the loop dispatches one step more
    before it has read the last of them.  The first dispatch after the
    admission has nothing ahead of it; every other one has."""
    model, variables = tiny
    n = 9
    clear_trace()
    with Server(model, variables, max_batch=2) as server:
        out = server.complete(_prompt(41, 5), n, timeout=120)
        snap = _when_all_landed(server)
        server.metrics.publish()
    assert len(out) == 5 + n
    dispatches = _events("serve_decode.dispatch")
    assert [d["args"]["ahead"] for d in dispatches] == [0] + [1] * (n - 1)
    assert snap["decode_steps_total"] == n
    assert snap["decode_steps_ahead_total"] == n - 1
    delivers = _events("serve_deliver")
    assert [e["args"]["dropped"] for e in delivers] == [0] * (n - 1) + [1]
    assert sum(e["args"]["emitted"] for e in delivers) == n - 1
    assert snap["decode_rows_dropped_total"] == 1
    assert snap["tokens_total"] == n
    # A dropped row is no live slot: the last step carried nobody.
    assert list(server.metrics._occupancy) == [0.5] * (n - 1) + [0.0]
    from ml_trainer_tpu.telemetry.registry import default_registry

    exposition = default_registry().prometheus_text()
    assert "serving_decode_steps_ahead_total" in exposition
    assert "serving_decode_rows_dropped_total" in exposition


def test_a_step_sample_is_never_less_than_the_step(tiny):
    """``record_step``'s seconds divide rooflines, so they may never be
    less than the device's time.  The stub stands in for a device that
    runs one step at a time and takes ``slow`` seconds for each (a step is
    ready ``slow`` after the later of its dispatch and the step before it,
    and its fence waits until then) under a host that takes ``host``
    seconds to dispatch.  The period between two landings is ``slow``:
    the wait in the fence alone is less, dispatch to landing spans two.
    Summed, the samples are never under the device's time (a landing the
    host came late to shortens the NEXT sample by as much as it lengthens
    its own); beside the prefills they never exceed the wall time."""
    model, variables = tiny
    slow, host = 0.03, 0.01

    class Kept(ServingMetrics):
        def __init__(self):
            super().__init__()
            self.steps, self.prefills = [], []

        def record_step(self, seconds, *rest):
            self.steps.append(seconds)
            super().record_step(seconds, *rest)

        def record_prefill(self, seconds, tokens=1):
            self.prefills.append(seconds)
            super().record_prefill(seconds, tokens)

    metrics = Kept()
    with Server(model, variables, max_batch=2, metrics=metrics) as server:
        server.complete(_prompt(42, 4), 3, timeout=120)      # warm
        warm = _when_all_landed(server)
        del metrics.steps[:], metrics.prefills[:]
        engine, ready = server.engine, [0.0]
        real_dispatch, real_fence = engine._dispatch, engine._fence

        def dispatch():
            time.sleep(host)
            real_dispatch()
            ready[0] = max(time.perf_counter(), ready[0]) + slow
            engine._flying[-1].ready_at = ready[0]

        def fence(landing):
            time.sleep(max(0.0, landing.ready_at - time.perf_counter()))
            return real_fence(landing)

        engine._dispatch, engine._fence = dispatch, fence
        t0 = time.perf_counter()
        a = server.submit(_prompt(43, 5), 10)
        next(iter(a))
        b = server.submit(_prompt(44, 6), 6)      # an admission mid-decode
        a.result(timeout=120)
        b.result(timeout=120)
        snap = _when_all_landed(server)
        wall = time.perf_counter() - t0
    assert (snap["decode_steps_ahead_total"]
            - warm["decode_steps_ahead_total"]) >= 6
    assert len(metrics.steps) == (
        snap["decode_steps_total"] - warm["decode_steps_total"]) >= 10
    assert sum(metrics.steps) >= slow * len(metrics.steps)
    assert sorted(metrics.steps)[len(metrics.steps) // 2] >= slow - 2e-3
    assert len(metrics.prefills) == 2
    assert sum(metrics.steps) + sum(metrics.prefills) <= wall


@pytest.mark.parametrize("options", [
    dict(spec_k=2), dict(kv_page_size=PS),
    dict(kv_page_size=PS, spec_k=2)], ids=lambda o: "+".join(sorted(o)))
def test_speculative_and_paged_engines_never_dispatch_ahead(tiny, options):
    model, variables = tiny
    pA, pB = _prompt(45, 5), _prompt(46, 7)
    clear_trace()
    sw = Stepwise(model, variables, max_batch=2, **options)
    a, b = sw.submit(pA, 12), sw.submit(pB, 6)
    for _ in range(200):
        sw.turn()
        assert not sw.engine.in_flight()
        if a.request.finished_at and b.request.finished_at:
            break
    np.testing.assert_array_equal(
        a.result(timeout=5),
        np.asarray(generate(model, variables, pA[None], 12))[0])
    np.testing.assert_array_equal(
        b.result(timeout=5),
        np.asarray(generate(model, variables, pB[None], 6))[0])
    snap = sw.server.metrics.snapshot()
    assert snap["decode_steps_total"] > 0
    assert snap["decode_steps_ahead_total"] == 0
    assert snap["decode_rows_dropped_total"] == 0
    assert all(d["args"]["ahead"] == 0
               for d in _events("serve_decode.dispatch"))


def test_a_speculative_engine_told_to_stop_drafting_looks_ahead(tiny):
    """The decision is read from the engine's state at every turn: with
    drafting switched off (the overload ladder's rung 2) the vanilla step
    overlaps; switched on again, the step in flight lands first."""
    model, variables = tiny
    pA = _prompt(47, 5)
    sw = Stepwise(model, variables, max_batch=2, spec_k=2)
    sw.engine.spec_enabled = False
    a = sw.submit(pA, 20)
    _flying(sw, [a])
    sw.turn(3)                           # each dispatched ahead of a landing
    assert sw.engine.in_flight()
    sw.engine.spec_enabled = True
    sw.turn()
    assert not sw.engine.in_flight()
    sw.until_done([a])
    np.testing.assert_array_equal(
        a.result(timeout=5),
        np.asarray(generate(model, variables, pA[None], 20))[0])
    assert sw.server.metrics.snapshot()["decode_steps_ahead_total"] > 0
