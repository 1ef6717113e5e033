"""Driver for serving traffic: ``EdgeServer.submit`` / ``step`` from the
public API.

The model half comes from the file that the configuration's ``"arch"``
key names (``archs/<arch>.py``): the system's family, the parent weights
from the seed in one jitted call, the tenants' submodels, the vocabulary
and the plain reference. Set-up builds those, the submodel masks and one
``EdgeServer``; a warm batch of requests compiles its prefill,
cache-write and decode-step programs. The window then offers the mix's
load:

* ``arrival: poisson`` — an open loop at the mix's fixed rate for the
  window; every request due in it is served to its end (at most
  ``drain_s`` past the close), and its latency counts from when it was due;
* ``arrival: backlog`` — an offline batch whose queue is topped up so
  that it never empties; tokens delivered in the window count.

Tokens are timed when ``step()`` returns: a slot newly occupied got its
first token (from the prefill) and the step's decode token; a slot that
was already occupied got one more token.

``correct`` runs the model file's plain reference over a seeded sample
of finished requests, the longest among them, once the server is
freed.
"""
from __future__ import annotations

import collections
import gc
import shutil
import time
from typing import Dict, List

import jax
import numpy as np

from chipbench.harness import device, requests, seeds
from chipbench.harness.compile_meter import CompileMeter
from chipbench.harness.result import Check, Run, log
from chipbench.harness.spans import Spans


class Ledger:
    """Per-request and per-step timings, from the server's public API."""

    def __init__(self):
        self.req: Dict[int, Dict] = {}
        self.steps: List[Dict] = []
        self.done: Dict[int, object] = {}

    def submit(self, server, draw, spec, now: float) -> None:
        from repro.serving.batcher import Request
        server.submit(Request(uid=draw.uid, spec=spec, prompt=draw.prompt,
                              max_new_tokens=draw.output_len))
        self.req[draw.uid] = {"due": now, "draw": draw, "admit": None,
                              "deliveries": [], "tokens": 0}

    def step(self, server, spans: Spans) -> int:
        b = server.batcher
        before = {b.request_at(s).uid for s in b.occupied()}
        ts = time.perf_counter()
        with spans.span("step"):
            finished = server.step()
        te = time.perf_counter()
        after = {b.request_at(s).uid for s in b.occupied()}
        ended = {c.uid: c for c in finished}
        admitted = (after | set(ended)) - before
        got, ctx = 0, []
        for uid in sorted(after | set(ended)):
            r = self.req[uid]
            if uid in admitted:
                r["admit"] = ts
            n = (len(ended[uid].tokens) - r["tokens"]) if uid in ended \
                else (2 if uid in admitted else 1)
            r["tokens"] += n
            r["deliveries"].append(te)
            got += n
            ctx.append((uid, r["draw"].prompt_len + r["tokens"] - 1))
        self.done.update(ended)
        self.steps.append({"t0": ts, "t1": te, "admitted": sorted(admitted),
                           "tokens": got, "decoded": ctx})
        return got


def build(cell, seed: int, spans: Spans):
    """Set-up: weights from the seed, the tenants' submodels, one
    EdgeServer, and a warm batch through its three programs."""
    from repro.serving.batcher import Request
    from repro.serving.server import EdgeServer

    from chipbench.harness.bench import apply_precision
    apply_precision(cell.config)
    arch, mix = cell.arch(), cell.traffic
    family = arch.family(cell)
    params = arch.init_params(cell, seed)
    # shapes only: the key's value does not matter
    want = jax.eval_shape(family.init_params, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(params))):
        raise ValueError("the reference's weight layout is not the "
                         "system's")
    specs = arch.tenant_specs(cell, seed)
    prog_specs = [arch.program_spec(s) for s in specs]
    with spans.span("setup.masks"):
        for s in prog_specs:
            family.decode_masks(s)
    server = EdgeServer(family, params, slots=mix["slots"],
                        prompt_len=mix["prompt_len"],
                        max_new_tokens=mix["max_new_tokens"])
    wrng = seeds.stream(seed, 21)
    for i in range(mix["slots"]):
        server.submit(Request(uid=-1 - i, spec=prog_specs[i % len(specs)],
                              prompt=wrng.integers(0, arch.vocab_size(cell),
                                                   mix["prompt_len"],
                                                   dtype=np.int32),
                              max_new_tokens=3))
    while server.batcher.busy:
        server.step()
    return server, specs, prog_specs


def spec_index(specs):
    """Tenant t uses submodel t mod len(specs)."""
    return lambda draw: draw.tenant % len(specs)


def run(cell, run: Run, ctx: Dict) -> None:
    mix, vocab = cell.traffic, cell.arch().vocab_size(cell)
    meter, spans = CompileMeter(), Spans()
    run.spans = spans
    server, specs, prog_specs = build(cell, run.seed, spans)
    spec_of = spec_index(specs)
    progs = server.compiled_programs()

    # -- the window --------------------------------------------------------
    ledger = Ledger()
    seconds = float(mix["trace_seconds"]) if run.traced else run.seconds
    n0 = meter.n
    pauses = GcPauses()
    if run.traced:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
        jax.profiler.start_trace(ctx["trace_dir"])
    gc.callbacks.append(pauses)
    t0 = time.perf_counter()
    run.setup_s = time.time() - ctx["process_start"]
    with spans.span("window"):
        if mix["arrival"] == "poisson":
            window_tokens = _open_loop(server, ledger, spans, mix, run.seed,
                                       seconds, vocab,
                                       prog_specs, spec_of, t0)
            t1 = t0 + seconds
        else:
            window_tokens, t1 = _backlog(server, ledger, spans, mix,
                                         run.seed, seconds, vocab,
                                         prog_specs, spec_of, t0)
    gc.callbacks.remove(pauses)
    if run.traced:
        jax.profiler.stop_trace()
    run.window = (t0, t1)
    run.window_s = t1 - t0
    run.counters.update({"compiles_in_window": meter.n - n0,
                         "window_tokens": window_tokens,
                         "prompt_len": mix["prompt_len"],
                         "slots": mix["slots"]})
    run.steps = ledger.steps
    run.extra = {"ledger": ledger, "specs": specs, "spec_of": spec_of,
                 "cell": cell, "mix": mix}
    _end_to_end(run, ledger, mix, window_tokens)
    run.memory_peak_bytes = device.memory_peak_bytes(ctx["devices"])
    log(f"programs {progs} -> {server.compiled_programs()}; compiles in "
        f"window {meter.n - n0}; steps {len(ledger.steps)}; finished "
        f"{len(ledger.done)}")
    log_steps(ledger.steps, t0, pauses)
    if run.traced:
        from chipbench.harness import trace
        run.trace = trace.reduce(ctx["trace_dir"], cell.chips)
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)

    # -- correctness: free the server, then the reference -----------------
    del server
    gc.collect()
    run.checks = compare(cell, run.seed, ledger, specs, spec_of)


class GcPauses:
    """The interpreter's garbage-collector pauses, from its callbacks:
    (start, seconds, generation) for each collection."""

    def __init__(self):
        self.pauses: List = []
        self._start = None

    def __call__(self, phase: str, info: Dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.pauses.append((self._start, now - self._start,
                                info["generation"]))
            self._start = None

    def within(self, a: float, b: float) -> float:
        return sum(d for s, d, _ in self.pauses if a <= s < b)


def log_steps(steps: List[Dict], t0: float, pauses: GcPauses,
              n: int = 5) -> None:
    """The per-step record in brief, so that a run whose tail reads far
    off shows where its time went: step times, the slowest steps with the
    garbage collector's time inside each, and the longest gaps between
    one step's end and the start of a next step that had slots decoding
    already (an idle open loop sleeps until the next arrival, which is no
    stall)."""
    if not steps:
        return
    ms = np.array([(s["t1"] - s["t0"]) * 1e3 for s in steps])
    gen2 = [d for _, d, g in pauses.pauses if g == 2]
    log(f"step_ms p50 {np.percentile(ms, 50):.3f} p99 "
        f"{np.percentile(ms, 99):.3f} max {ms.max():.3f}; gc "
        f"{len(pauses.pauses)} collections, "
        f"{sum(d for _, d, _ in pauses.pauses) * 1e3:.3f} ms, "
        f"{len(gen2)} of generation 2, longest "
        f"{max((d for _, d, _ in pauses.pauses), default=0) * 1e3:.3f} ms")
    for i in np.argsort(ms)[::-1][:n]:
        s = steps[i]
        log(f"slow step at {s['t0'] - t0:.3f} s: {ms[i]:.3f} ms, "
            f"admitted {len(s['admitted'])}, decoding {len(s['decoded'])}, "
            f"gc {pauses.within(s['t0'], s['t1']) * 1e3:.3f} ms")
    gaps = np.array([(b["t0"] - a["t1"]) * 1e3
                     if len(b["decoded"]) > len(b["admitted"]) else 0.0
                     for a, b in zip(steps, steps[1:])])
    for i in np.argsort(gaps)[::-1][:n]:
        log(f"gap after step at {steps[i]['t1'] - t0:.3f} s: "
            f"{gaps[i]:.3f} ms")


def _open_loop(server, ledger, spans, mix, seed, seconds, vocab,
               prog_specs, spec_of, t0) -> int:
    draws = collections.deque(requests.open_loop(mix, seed, seconds, vocab))
    tokens = 0
    deadline = seconds + float(mix["drain_s"])
    while True:
        now = time.perf_counter() - t0
        while draws and draws[0].due <= now:
            d = draws.popleft()
            ledger.submit(server, d, prog_specs[spec_of(d)], t0 + d.due)
        if server.batcher.busy:
            tokens += ledger.step(server, spans)
        elif draws:
            time.sleep(max(0.0, draws[0].due - now))
        else:
            break
        if now > deadline:
            break
    return tokens


def _backlog(server, ledger, spans, mix, seed, seconds, vocab, prog_specs,
             spec_of, t0):
    gen = requests.backlog(mix, seed, vocab)
    waiting = 0
    tokens = 0
    while True:
        while waiting < mix["slots"]:
            d = next(gen)
            ledger.submit(server, d, prog_specs[spec_of(d)], t0)
            waiting += 1
        tokens += ledger.step(server, spans)
        waiting -= len(ledger.steps[-1]["admitted"])
        if ledger.steps[-1]["t1"] - t0 >= seconds:
            break
    t1 = ledger.steps[-1]["t1"]
    drain_until = time.perf_counter() + float(mix["drain_s"])
    while server.batcher.busy and time.perf_counter() < drain_until:
        ledger.step(server, spans)
    return tokens, t1


def _end_to_end(run: Run, ledger: Ledger, mix: Dict, tokens: int) -> None:
    from chipbench.harness.stats import percentile
    reqs = list(ledger.req.values())
    if mix["arrival"] == "poisson":
        cap = run.window[0] + run.seconds + float(mix["drain_s"])
        ttft, gaps, failed = [], [], 0
        for r in reqs:
            dl = r["deliveries"]
            if r["draw"].uid not in ledger.done:
                failed += 1
            ttft.append(((dl[0] if dl else cap) - r["due"]) * 1e3)
            gaps.extend((b - a) * 1e3 for a, b in zip(dl, dl[1:]))
        run.attempted, run.failed = len(reqs), failed
        run.end_to_end["ttft_p95_ms"] = percentile(ttft, 95)
        run.end_to_end["itl_p95_ms"] = percentile(gaps, 95)
        log(f"requests {len(reqs)} failed {failed} ttft_p50_ms "
            f"{percentile(ttft, 50):.3f} itl_p50_ms "
            f"{percentile(gaps, 50):.3f}")
    else:
        started = [r for r in reqs if r["admit"] is not None]
        run.attempted = len(started)
        run.failed = sum(1 for r in started
                         if r["draw"].uid not in ledger.done)
        run.end_to_end["decode_tok_s"] = tokens / run.window_s
    run.end_to_end["setup_s"] = run.setup_s


def check_sample(ledger: Ledger, seed: int, max_requests: int) -> List:
    """A seeded sample of ``max_requests`` finished requests, the longest
    among them."""
    done = sorted(ledger.done.values(), key=lambda c: c.uid)
    if not done:
        return []
    longest = max(done, key=lambda c: (len(c.tokens), -c.uid))
    rest = [c for c in done if c.uid != longest.uid]
    order = seeds.stream(seed, 31).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max_requests - 1]]


def served_prompt(prompt: np.ndarray, window: int) -> np.ndarray:
    """The prompt as served: its last ``window`` tokens, front-padded with
    token 0 to the window."""
    p = np.asarray(prompt, np.int32)[-window:]
    return np.concatenate([np.zeros(window - len(p), np.int32), p])


def reference_gaps(cell, seed: int, sample: List, specs: List, spec_of,
                   ledger: Ledger, *, control=None):
    """Widest gap of the served tokens, by the model file's reference;
    with ``control`` (dtype, matmul precision) also the widest gap of the
    tokens that the reference computed that way puts first at the same
    positions. Each request is handed over as the server saw it: its
    prompt in the server's window, its tokens and its submodel."""
    mix = cell.traffic
    served = [(served_prompt(comp.prompt, mix["prompt_len"]), comp.tokens,
               specs[spec_of(ledger.req[comp.uid]["draw"])])
              for comp in sample]
    return cell.arch().reference_gaps(
        cell, seed, served, mix["prompt_len"] + mix["max_new_tokens"] - 1,
        control=control)


def compare(cell, seed: int, ledger: Ledger, specs, spec_of) -> List[Check]:
    mix = cell.traffic
    t = time.perf_counter()
    sample = check_sample(ledger, seed, int(mix["check_requests"]))
    if not sample:
        return [Check("served_gap", float("nan"),
                      float(cell.limits["served_gap"]))]
    widest, _, n = reference_gaps(cell, seed, sample, specs, spec_of, ledger)
    log(f"reference: {len(sample)} requests, {n} served tokens, "
        f"{time.perf_counter() - t:.3f} s")
    return [Check("served_gap", widest, float(cell.limits["served_gap"]))]
