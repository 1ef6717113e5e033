"""repro.obs: program-side spans and counters at the layer boundaries of
the CFL round and EdgeServer.step — off by default, free when off, and
never a change to what the program computes."""
import dataclasses
import glob
import os
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import ARCHS, reduced
from repro.configs.paper_cnn import CNNConfig
from repro.core.elastic import family_for
from repro.core.search import SearchConfig
from repro.core.latency import EDGE_FLEET
from repro.fl import CFLConfig, CFLSession
from repro.fl.client import ClientInfo
from repro.serving import EdgeServer, Request

CNN = CNNConfig(name="obs-test", in_channels=1, image_size=16,
                stem_channels=4, stages=((8, 1), (16, 1)),
                groupnorm_groups=2, gate_hidden=8,
                elastic_widths=(0.5, 1.0))


@pytest.fixture(autouse=True)
def _fresh():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _children(recs, i):
    return [r.name for r in recs if r.parent == i]


def _ancestors(recs, i):
    out = []
    while recs[i].parent >= 0:
        i = recs[i].parent
        out.append(recs[i].name)
    return out


def test_disabled_span_is_shared_null_and_keeps_nothing():
    a, b = obs.span("x"), obs.span("y", uid=3)
    assert a is b
    with a:
        pass
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for i in range(10000):
        with obs.span("engine.pack"):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                if s.traceback[0].filename == obs.__file__)
    assert grown <= 0
    assert obs.records() == [] and obs.increments() == []


def test_nesting_sets_parents_and_record_and_counters():
    obs.count("c")                          # counters are always on
    assert obs.counters() == {"c": 1} and obs.increments() == []
    obs.enable()
    assert obs.active()
    with obs.span("outer", round=7):
        with obs.span("inner"):
            obs.count("c", 2)
        obs.record("queue", 10, 20, uid=5)
    with obs.span("second"):
        pass
    recs = obs.records()
    assert [r.name for r in recs] == ["outer", "inner", "queue", "second"]
    assert [r.parent for r in recs] == [-1, 0, 0, -1]
    assert recs[0].attrs == {"round": 7} and recs[2].attrs == {"uid": 5}
    assert (recs[2].start_ns, recs[2].end_ns) == (10, 20)
    assert recs[0].start_ns <= recs[1].start_ns <= recs[1].end_ns \
        <= recs[0].end_ns
    assert obs.counters()["c"] == 3
    (inc,) = obs.increments()
    assert (inc.name, inc.n, inc.parent) == ("c", 2, 1)
    obs.disable()
    with obs.span("off"):
        pass
    assert len(obs.records()) == 4


def test_compile_is_recorded_under_the_open_span():
    f = jax.jit(lambda x: x * 3 + 1)
    n0 = obs.counters().get("compile.count", 0)
    obs.enable()
    with obs.span("step"):
        f(jnp.arange(5.0)).block_until_ready()
    recs = obs.records()
    compiles = [r for r in recs if r.name == "compile"]
    assert compiles and all(recs[r.parent].name == "step"
                            for r in compiles)
    assert all(r.end_ns > r.start_ns for r in compiles)
    assert obs.counters()["compile.count"] >= n0 + 1
    assert obs.counters()["compile.seconds"] > 0


def test_profiler_capture_turns_spans_on(tmp_path):
    """Under jax.profiler.trace the spans record without enable() and
    land in the trace as repro.<name> host events."""
    assert not obs.active()
    with jax.profiler.trace(str(tmp_path)):
        assert obs.active()
        with obs.span("engine.pack", round=2):
            jnp.ones(3).block_until_ready()
    assert not obs.active()
    assert [r.name for r in obs.records() if r.name != "compile"] == \
        ["engine.pack"]
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    names = {ev.name
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert any(n.startswith("repro.engine.pack") for n in names), names


def _server():
    cfg = reduced(ARCHS["granite-3-8b"], n_layers=2, d_model=64)
    fam = family_for(dataclasses.replace(cfg, vocab_size=256))
    params = fam.init_params(jax.random.PRNGKey(0))
    return EdgeServer(fam, params, slots=2, prompt_len=8, max_new_tokens=4)


def _requests(vocab):
    rng = np.random.default_rng(0)
    return [Request(uid=100 + i, spec=None,
                    prompt=rng.integers(0, vocab, 8, dtype=np.int32),
                    max_new_tokens=3) for i in range(3)]


def test_edge_server_step_spans_and_queue_records():
    server = _server()
    reqs = _requests(server.cfg.vocab_size)
    off = server.run(reqs)
    assert obs.records() == []
    obs.enable()
    steps = 0
    for r in reqs:
        server.submit(r)
    on = []
    while server.batcher.busy:
        on.extend(server.step())
        steps += 1
    recs = obs.records()
    step_ix = [i for i, r in enumerate(recs) if r.name == "serve.step"]
    assert len(step_ix) == steps
    assert all(recs[i].parent == -1 for i in step_ix)
    first = _children(recs, step_ix[0])
    assert first == ["serve.queue", "serve.admit", "serve.queue",
                     "serve.admit", "serve.stack_masks",
                     "serve.decode_dispatch", "serve.logits_wait",
                     "serve.sample"]
    assert recs[step_ix[0]].attrs == {"admitted": 2, "active": 2}
    admits = [i for i, r in enumerate(recs) if r.name == "serve.admit"]
    assert len(admits) == len(reqs)
    for i in admits:
        assert _children(recs, i) == ["serve.masks", "serve.prefill",
                                      "serve.write",
                                      "serve.first_token_wait"]
    queue = {r.attrs["uid"]: r for r in recs if r.name == "serve.queue"}
    assert set(queue) == {r.uid for r in reqs}
    admit_at = {recs[i].attrs["uid"]: recs[i].start_ns for i in admits}
    for uid, q in queue.items():
        assert q.start_ns <= q.end_ns <= admit_at[uid]
    # the third request waited for a slot: a step longer than the others
    assert queue[102].end_ns - queue[102].start_ns > \
        queue[100].end_ns - queue[100].start_ns
    # tracing changes no served token
    assert sorted((c.uid, c.tokens) for c in on) == \
        sorted((c.uid, c.tokens) for c in off)


def _population(n_clients=2, n_train=64, n_test=32):
    rng = np.random.default_rng(0)

    def data(n):
        return {"x": rng.random((n, 16, 16, 1), np.float32),
                "y": rng.integers(0, 10, n).astype(np.int32)}
    devices = [p.name for p in EDGE_FLEET]
    clients = [ClientInfo(cid=i, device=devices[i % len(devices)],
                          quality=i % 5, n_samples=n_train,
                          latency_bound=1e9) for i in range(n_clients)]
    return (clients, [data(n_train) for _ in clients],
            [data(n_test) for _ in clients])


def _session(population):
    clients, train, test = population
    fam = family_for(CNN)
    fl = CFLConfig(n_workers=len(clients), local_epochs=1, batch_size=32,
                   lr=0.05, seed=0)
    params = jax.jit(fam.init_params)(jax.random.PRNGKey(0))
    return CFLSession(fam, clients, train, test, fl, params=params)


@pytest.fixture(scope="module")
def cfl_runs():
    """The same two rounds with spans off and on: (params, history,
    records, increments, predict_rows calls) of each."""
    out, population = {}, _population()
    for on in (False, True):
        obs.reset()
        sess = _session(population)
        pred = sess.server.predictor
        calls = []
        inner = pred.predict_rows

        def counted(*a, **k):
            calls.append(1)
            return inner(*a, **k)
        pred.predict_rows = counted
        if on:
            obs.enable()
        sess.run(2)
        obs.disable()
        out[on] = (jax.tree.map(np.asarray, sess.params),
                   [dict(r) for r in sess.history], obs.records(),
                   obs.increments(), len(calls))
    obs.reset()
    return out


def test_cfl_round_spans_and_predict_counter(cfl_runs):
    _, _, recs, incs, calls = cfl_runs[True]
    rounds = [i for i, r in enumerate(recs) if r.name == "cfl.round"]
    assert [recs[i].attrs["round"] for i in rounds] == [0, 1]
    for i in rounds:
        kids = _children(recs, i)
        for name in ("cfl.select", "cfl.search", "engine.broadcast",
                     "engine.pack", "engine.dispatch", "engine.wait",
                     "engine.aggregate", "cfl.post_aggregate",
                     "cfl.bookkeep"):
            assert name in kids, (name, kids)
    packs = [i for i, r in enumerate(recs) if r.name == "engine.pack"]
    for i in packs:
        assert _children(recs, i) == ["engine.masks", "engine.data",
                                      "engine.stream"]
    post = [i for i, r in enumerate(recs) if r.name == "cfl.post_aggregate"]
    for i in post:
        assert _children(recs, i) == ["predictor.add", "predictor.train"]
    # round 0 draws random specs; round 1 runs the workers' GAs in
    # lockstep, one span per generation
    gens = [i for i, r in enumerate(recs)
            if r.name == "cfl.search.generation"]
    assert len(gens) == SearchConfig().generations
    assert all(_ancestors(recs, i)[:2] == ["cfl.search", "cfl.round"]
               for i in gens)
    assert {recs[recs[recs[i].parent].parent].attrs["round"]
            for i in gens} == {1}
    pcalls = [x for x in incs if x.name == "search.predict_calls"]
    assert calls > 0 and sum(x.n for x in pcalls) == calls
    assert all(recs[x.parent].name == "search.predict" for x in pcalls)
    assert all(_ancestors(recs, x.parent)[0] == "cfl.search.generation"
               for x in pcalls)
    rows = [x for x in incs if x.name == "search.predict_rows"]
    assert len(rows) == calls and all(x.n >= 1 for x in rows)
    assert all(r.end_ns is not None for r in recs)


def test_spans_change_no_round_result(cfl_runs):
    p_off, h_off, recs_off, _, calls_off = cfl_runs[False]
    p_on, h_on, _, _, calls_on = cfl_runs[True]
    assert recs_off == [] and calls_off == calls_on
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        assert np.array_equal(a, b)
    for a, b in zip(h_off, h_on):
        assert a["accs"] == b["accs"] and a["specs"] == b["specs"]
