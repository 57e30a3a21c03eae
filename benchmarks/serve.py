"""Traffic kind `serve`: `PredictorServer` over HTTP in this process, a
`PagedKVEngine` behind it, and a closed loop of streaming clients.

Every seed sends the same requests: prompt and output lengths at the even
quantiles of two log-uniform laws, paired by the mix's `pair_stride`. The
seed deals their order and draws the token ids. Each client's first request
is cut to a seeded share of its output, so that the window opens on slots at
all phases of their requests and not on a synchronised start. (A tick takes
longer the longer the live contexts are, so a seed that also paired the
lengths anew changed the work of a window by several percent: PERF.md.)

The clients stamp every token line as it arrives. A tick of the engine
delivers `steps_per_tick` tokens to each live stream at once, so arrivals
come in bursts. The end-to-end rate is every token that arrived after the
window's first burst, up to its last, over the time between those two: whole
bursts, so that a tick more or less at the window's edge does not move it.
Only the two edges matter to it: the tokens of the first burst and the first
arrival of the last. What silence ends a burst is read from the stamps alone
(`burst_gap_s`): the widest hole among the silences between neighbouring
arrivals, so the cut follows the tick's length, whatever a later PR makes of
it, and reads no counter of the program.
"""
from __future__ import annotations

import http.client
import json
import math
import threading
import time

import numpy as np


def log_uniform_grid(lo, hi, n):
    """n lengths at the even quantiles of a log-uniform law on [lo, hi]."""
    return [int(round(lo * (hi / lo) ** ((i + 0.5) / n))) for i in range(n)]


def deal(traffic, vocab, seed):
    """([(prompt ids, new tokens)], [share of its first output a client
    still has to run]). The requests are the same for every seed: prompt
    length i goes with output length i * `pair_stride` (mod n), so long
    prompts meet long and short outputs alike. The seed deals their order,
    the clients' phases and the token ids."""
    n = traffic["distinct_requests"]
    prompts = log_uniform_grid(*traffic["prompt_tokens"], n)
    outputs = log_uniform_grid(*traffic["output_tokens"], n)
    pairs = [(prompts[i], outputs[i * traffic["pair_stride"] % n])
             for i in range(n)]
    rng = np.random.default_rng(seed)
    rng.shuffle(pairs)
    left = [(c + 0.5) / traffic["clients"] for c in range(traffic["clients"])]
    rng.shuffle(left)
    work = [(rng.integers(1, vocab, size=p).tolist(), o) for p, o in pairs]
    return work, left


def client_work(work, left, c, traffic):
    """Client c's requests in order, for as long as it asks: every
    `clients`-th of the deal, round and round, the first cut to what is
    left of it when the window opens."""
    n, per = traffic["clients"], traffic["engine"]["steps_per_tick"]
    ids, new = work[c % len(work)]
    yield ids, max(per, int(np.ceil(left[c] * new)))
    k = 1
    while True:
        yield work[(c + k * n) % len(work)]
        k += 1


def bucket(n):
    """The engine's prefill bucket: the next power of two, at least 8."""
    return max(8, 1 << (n - 1).bit_length())


class Client(threading.Thread):
    """One closed-loop caller: sends its next request when the last ends."""

    def __init__(self, port, work, closing):
        super().__init__(daemon=True)
        self.port, self.work, self.closing = port, work, closing
        self.streams = []       # (prompt tokens, [arrival times], end | None)
        self.errors = []

    def run(self):
        for ids, new in self.work:
            if self.closing.is_set():
                return
            arrivals = []
            self.streams.append([len(ids), arrivals, None])
            try:
                self._one(ids, new, arrivals)
                self.streams[-1][2] = time.perf_counter()
            except Exception as e:      # noqa: BLE001 - counted as failed
                self.errors.append((time.perf_counter(), repr(e)))

    def _one(self, ids, new, arrivals):
        body = json.dumps({"ids": [ids], "max_new_tokens": new,
                           "stream": True})
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=600)
        try:
            conn.request("POST", "/generate", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}")
            done = False
            for line in resp:
                now = time.perf_counter()
                if not line.strip():
                    continue
                obj = json.loads(line)
                if "error" in obj:
                    raise RuntimeError(obj["error"])
                if "tokens" in obj:
                    arrivals.append(now)
                done = done or bool(obj.get("done"))
            # a stream the benchmark cancelled at the end of the window
            # ends early and in order; any other short stream is a failure
            if not self.closing.is_set() and (not done
                                              or len(arrivals) != new):
                raise RuntimeError(f"stream ended after {len(arrivals)} "
                                   f"of {new} tokens")
        finally:
            conn.close()


def _cancel_running(eng):
    """End every request the engine still holds, so that the server ends
    each stream in order and no socket is reset. The engine has no public
    call for it yet (PERF.md, Open questions)."""
    with eng._lock:
        held = list(eng._pending)
    held += [s.req for s in eng._slots if s is not None]
    for req in held:
        req.cancel()


def check_against_reference(builder, model, cfg, eng, traffic, seed):
    """One seeded request through the engine (prefill, then decode through
    the paged cache), then the reference's full forward over prompt +
    output. Tokens cannot be compared: with random weights the best logit
    changes on rounding. Compared is how far, in standard deviations of
    that position's logits, the reference's logit of the server's token
    lies under the reference's best logit."""
    import jax
    from paddle_tpu.jit.functional import state_arrays
    c = traffic["check"]
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(1, cfg["vocab_size"], size=c["prompt_tokens"])
    got = eng.generate([prompt.astype(np.int32)],
                       max_new_tokens=c["new_tokens"])[0]
    ids = np.concatenate([prompt, np.asarray(got[:-1], np.int64)]
                         ).astype(np.int32)
    lg = jax.jit(lambda p, row: builder.reference.logits(p, cfg, row))(
        state_arrays(model), ids)
    lg = np.asarray(lg[len(prompt) - 1:], np.float64)
    chosen = lg[np.arange(len(got)), np.asarray(got)]
    gaps = (lg.max(-1) - chosen) / lg.std(-1)
    return float(gaps.max()), float(gaps.mean()), len(got)


def bursts(times, gap_s):
    """[(first arrival, tokens, last arrival)] of each burst of the sorted
    arrivals: a silence longer than `gap_s` ends a burst."""
    out = []
    for t in times:
        if out and t - out[-1][2] <= gap_s:
            out[-1][1] += 1
            out[-1][2] = t
        else:
            out.append([t, 1, t])
    return [tuple(b) for b in out]


def burst_gap_s(arrivals, tokens_per_tick):
    """The silence that ends a burst, from the sorted stamps alone: the
    silences between neighbouring arrivals are of two kinds, those inside a
    burst (a tick's tokens written one after another) and those between two
    bursts (the device at work), with a hole between the kinds; the gap is
    the middle, by ratio, of the widest hole. Only cuts are looked at that
    leave between half and three times as many bursts as the tokens make
    full ticks (`tokens_per_tick` from the traffic mix: clients x steps a
    tick; a prefill's first token may be a burst of its own, a tick with
    idle slots hands out fewer), so the arrivals can never all merge into
    one burst, whatever the tick's length, where a constant (100 ms until
    PR 27) merges them all once ticks come closer than itself."""
    silences = sorted((b - a for a, b in zip(arrivals, arrivals[1:])),
                      reverse=True)
    full = len(arrivals) / tokens_per_tick
    lo, hi = max(2, int(full / 2)), min(len(silences), int(3 * full) + 1)
    if lo >= hi:
        raise RuntimeError(f"the window holds {len(arrivals)} tokens, "
                           f"{full:.1f} ticks' worth: too few to report")
    floor = 1e-6        # stamps closer than the clock tells apart
    # cutting at the k widest silences leaves k + 1 bursts
    k = max(range(lo, hi), key=lambda k: silences[k - 1]
            / max(silences[k], floor))
    return math.sqrt(silences[k - 1] * max(silences[k], floor))


def rate_over_bursts(arrivals, gap_s):
    """(tokens/s, the bursts): every token after the window's first burst,
    up to its last, over the time between the two bursts' first arrivals.
    A burst split or merged in the middle moves nothing; one at an edge
    moves the count by at most a tick's tokens."""
    bs = bursts(arrivals, gap_s)
    if len(bs) < 3:
        raise RuntimeError(f"the window holds {len(bs)} bursts of "
                           f"{len(arrivals)} tokens: too few to report")
    return sum(b[1] for b in bs[1:]) / (bs[-1][0] - bs[0][0]), bs


def run(cell, args, clock, clog, log):
    import jax

    from paddle_tpu.inference import PagedKVEngine, PredictorServer

    cfg, traffic, builder = cell["config"], cell["traffic"], cell["builder"]
    geo = traffic["engine"]
    model = builder.build(cfg, args.seed, dtype="bfloat16",
                          seq=traffic["prompt_tokens"][1],
                          settings=traffic["model_settings"])
    model.eval()
    clock.mark("build")
    eng = PagedKVEngine(model, max_slots=geo["max_slots"],
                        page_size=geo["page_size"],
                        num_pages=geo["num_pages"],
                        max_pages_per_slot=geo["max_pages_per_slot"],
                        steps_per_tick=geo["steps_per_tick"], kernel=None)
    log("[engine]", dict(geo, decode_kernel=eng.decode_kernel))
    if not args.rehearse and eng.decode_kernel != geo["decode_kernel"]:
        raise RuntimeError(f"the engine chose the {eng.decode_kernel!r} "
                           f"decode path, the cell is {geo['decode_kernel']!r}")
    clock.mark("engine")

    # the check's request warms the tick and one prefill program
    worst, mean_gap, n_checked = check_against_reference(
        builder, model, cfg, eng, traffic, args.seed)
    clock.mark("check")

    # every other prefill program the window can ask for: the buckets of
    # the seed's own prompts, alone and as a pair, which takes whichever
    # width the engine gives a group of that bucket (one width a bucket,
    # however many meet: `paged.prefill_width`)
    work, left = deal(traffic, cfg["vocab_size"], args.seed)
    for b in sorted({bucket(len(ids)) for ids, _ in work}):
        one = next(ids for ids, _ in work if bucket(len(ids)) == b)
        eng.generate([one], max_new_tokens=1)
        eng.generate([one, one], max_new_tokens=1)
    clock.mark("warm_prefill")

    clients_n = traffic["clients"]
    closing = threading.Event()
    srv = PredictorServer(lambda inputs: inputs, host="127.0.0.1", port=0,
                          generator=eng).start()
    clients = [Client(srv.port, client_work(work, left, c, traffic), closing)
               for c in range(clients_n)]
    try:
        for c in clients:
            c.start()
        # ramp: every client streaming, and two more ticks gone by
        deadline = time.perf_counter() + 120
        ticks_at_full = None
        while time.perf_counter() < deadline:
            if any(c.errors for c in clients):
                raise RuntimeError(f"a client failed in the ramp: "
                                   f"{[c.errors for c in clients]}")
            if ticks_at_full is None and all(
                    c.streams and c.streams[0][1] for c in clients):
                ticks_at_full = eng.stats["ticks"]
            if ticks_at_full is not None \
                    and eng.stats["ticks"] >= ticks_at_full + 2:
                break
            time.sleep(0.02)
        else:
            raise RuntimeError("the ramp did not fill the slots in 120 s")
        clock.mark("ramp")
        setup_s = clock.total()

        # -- the measured window ----------------------------------------
        mark = clog.mark()
        # what the family counts itself, read as the window opens and closes
        counters = getattr(builder, "counters", lambda _eng: {})
        stats0, counted0 = dict(eng.stats), counters(eng)
        t0 = time.perf_counter()
        if args.trace:
            time.sleep(args.seconds / 3)
            cell["tracer"].record(
                lambda: time.sleep(traffic["trace_seconds"]))
        time.sleep(max(0.0, t0 + args.seconds - time.perf_counter()))
        t1 = time.perf_counter()
        stats1, counted1 = dict(eng.stats), counters(eng)
        compiled_in_window = clog.since(mark)["programs"]
        closing.set()
        give_up = time.perf_counter() + 60
        while any(c.is_alive() for c in clients) \
                and time.perf_counter() < give_up:
            _cancel_running(eng)    # again: a request may just have arrived
            time.sleep(0.05)
        stuck = sum(c.is_alive() for c in clients)
    finally:
        closing.set()
        eng.stop()
        srv.stop()
    if stuck:
        raise RuntimeError(f"{stuck} clients never returned")

    # -- reduce what the clients saw ------------------------------------
    arrivals, gaps, context_sum = [], [], 0
    finished = failed = 0
    for c in clients:
        failed += sum(1 for t, _ in c.errors if t0 <= t <= t1)
        for prompt_n, times, end in c.streams:
            finished += end is not None and t0 <= end <= t1
            inside = [(k, t) for k, t in enumerate(times) if t0 <= t <= t1]
            arrivals += [t for _k, t in inside]
            context_sum += sum(prompt_n + k for k, _t in inside)
            gaps += [b - a for (_i, a), (_j, b) in zip(inside, inside[1:])]
    arrivals.sort()
    # every counter the engine keeps, and the builder's own; a key that is
    # a maximum or a level (`tick_max_s`) has a difference that means nothing
    delta = {k: v - stats0.get(k, 0) for k, v in stats1.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    delta.update({k: v - counted0.get(k, 0) for k, v in counted1.items()})
    if len(gaps) < 20:
        raise RuntimeError(f"the window holds {len(gaps)} gaps between "
                           f"tokens: too few to report")
    gap_s = burst_gap_s(arrivals, clients_n * geo["steps_per_tick"])
    rate, bs = rate_over_bursts(arrivals, gap_s)
    gap_p95 = float(np.percentile(gaps, 95)) * 1000.0
    steps = max(1, delta["ticks"]) * geo["steps_per_tick"]
    widest = max((b - a for a, b in zip(arrivals, arrivals[1:])
                  if b - a <= gap_s), default=0.0)
    narrowest = min(b[0] - a[2] for a, b in zip(bs, bs[1:]))
    log("[window]", {"bursts": len(bs), "tokens": len(arrivals),
                     "tokens_per_s": rate,
                     "from_first_to_last_burst_s": bs[-1][0] - bs[0][0],
                     # the hole's two edges: any gap between them cuts
                     # the same bursts
                     "burst_gap_ms": gap_s * 1000.0,
                     "widest_gap_inside_a_burst_ms": widest * 1000.0,
                     "narrowest_gap_between_bursts_ms": narrowest * 1000.0,
                     "gaps": len(gaps), "gap_p50_ms":
                     float(np.percentile(gaps, 50)) * 1000.0,
                     "gap_p95_ms": gap_p95, "window_s": t1 - t0,
                     "compiled_in_window": compiled_in_window})
    log("[bursts] ms after the window opened, tokens:",
        [[round((t - t0) * 1000.0), n] for t, n, _last in bs])
    log("[window] engine", delta, "| requests finished", finished,
        "failed", failed)
    tol = traffic["check"]["tolerance_sd"]
    log(f"[correct] reference logit gap of the server's tokens, sd: max "
        f"{worst:.4f} mean {mean_gap:.4f} over {n_checked} positions "
        f"(tolerance {tol})")
    window = dict(delta, window_s=t1 - t0, rate=rate, gap_p95_ms=gap_p95,
                  live_context_tokens=context_sum / steps)
    return {
        "correct": bool(worst <= tol and failed == 0
                        and compiled_in_window == 0),
        "attempted": finished + failed, "failed": failed, "setup_s": setup_s,
        "end_to_end": {"serve_tokens_per_s": rate},
        "window": window,
        "compared": {"logit_gap_sd": (worst, tol), "failed": (failed, 0),
                     "compiled_in_window": (compiled_in_window, 0)},
    }
