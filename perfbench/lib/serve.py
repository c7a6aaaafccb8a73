"""The serving driver: open-loop arrivals into the program's StreamingEngine.

The harness submits each request when it is due and then calls
``engine.step()``; it stamps tokens itself, by reading each request's
output after ``step()`` returns, so no engine-internal timestamp is used.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from lib import device, model, traffic

CHIPS = (1,)                   # one engine, its weights on one chip


def requests_for(cell: dict, seed: int, seconds: float) -> list[dict]:
    wl, mix, cfg = cell["workload"], cell["traffic"], cell["config"]
    if mix["arrivals"].get("backlog"):
        n = wl["backlog"]
    else:
        n = max(1, round(wl["rate"] * seconds))
    return traffic.serve_requests(mix, cfg["vocab_size"], seed, n,
                                  wl.get("rate"))


def _outputs(eng) -> dict:
    """{request id: tokens emitted so far} of the requests in slots."""
    return {s.request_id: s.tokens for s in eng.active if s is not None}


def drive(eng, reqs: list[dict], seconds: float, tracer=None) -> dict:
    """Serve ``reqs`` for ``seconds``; returns the host's records."""
    from repro.obs.trace import span

    n = len(reqs)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    due = [t0 + r["due"] for r in reqs]
    rid_of: dict[int, int] = {}
    count = np.zeros(n, np.int64)
    first = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ticks = 0
    i = 0
    live: set[int] = set()
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now - t0)
        if now >= t_end:
            break
        with span("bench.submit"):
            while i < n and due[i] <= now:
                rid = eng.submit(reqs[i]["prompt"], reqs[i]["max_new"])
                rid_of[rid] = i
                i += 1
        if not eng.queue and not any(s is not None for s in eng.active):
            with span("bench.wait_arrival"):
                nxt = due[i] if i < n else t_end
                time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        eng.step()
        ticks += 1
        t = time.perf_counter()
        with span("bench.record"):
            outs = _outputs(eng)
            for rid in live | set(outs):
                toks = outs.get(rid)
                finished = toks is None
                if finished:
                    toks = eng.finished.get(rid, ())
                j = rid_of[rid]
                if len(toks) > count[j]:
                    if count[j] == 0:
                        first[j] = t
                    count[j] = len(toks)
                if finished and rid in eng.finished:
                    done[j] = t
            live = set(outs)
    t_close = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    return {"t0": t0, "t_close": t_close, "due": np.asarray(due),
            "count": count, "first": first, "done": done, "ticks": ticks, "rid_of": rid_of}


def summarize(rec: dict, seconds: float) -> dict:
    """End-to-end numbers from the host records of one window."""
    t0, t_close = rec["t0"], rec["t_close"]
    due = rec["due"]
    in_window = due < t0 + seconds
    first = rec["first"]
    ttft = np.where(np.isnan(first), t_close - due, first - due)[in_window]
    fin = ~np.isnan(rec["done"]) & (rec["count"] >= 2)
    tpot = ((rec["done"] - rec["first"])[fin] / (rec["count"][fin] - 1))
    return {
        "attempted": int(in_window.sum()),
        "completed": int((~np.isnan(rec["done"])).sum()),
        "ttft_s": ttft,
        "tpot_s": tpot,
        "tokens": int(rec["count"].sum()),
        "window_s": t_close - t0,
        "ticks": rec["ticks"],
    }


def check(cell: dict, seed: int, reqs: list[dict], rec: dict,
          finished: dict, ref, precision: str = "f32") -> dict:
    """Greedy tokens served in the window against the plain reference.

    A sample drawn from the seed of the requests finished in the window:
    the longest among them and others, ``check_requests`` in all.  (Fewer
    requests can be one long greedy loop, on which even the control's
    ranking agrees.)  The reference runs once over each prompt
    with its served tokens; the number compared is the widest gap by which
    a served token's reference logit lies below the reference's best.
    """
    wl, cfg = cell["workload"], cell["config"]
    vocab = cfg["vocab_size"]
    done = [(rid, toks) for rid, toks in finished.items()
            if rid in rec["rid_of"]]
    if not done:
        return {"ok": False, "why": "no request finished in the window"}
    for rid, toks in done:
        if not all(0 <= t < vocab for t in toks):
            return {"ok": False, "why": f"request {rid}: token outside the "
                                        "vocabulary"}
    total = lambda d: reqs[rec["rid_of"][d[0]]]["prompt"].size + len(d[1])
    done.sort(key=total)
    pick = [done.pop()]
    g = np.random.default_rng([seed, 1])
    pick += [done[k] for k in
             g.permutation(len(done))[:wl["check_requests"] - 1]]
    seqs = [np.concatenate([reqs[rec["rid_of"][rid]]["prompt"],
                            np.asarray(toks, np.int32)]) for rid, toks in pick]
    # The reference's shapes depend on the cell alone, so its programs
    # compile once and come from the cache in every later run.
    mix = cell["traffic"]
    width = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"])
              // 512) * 512
    tokens = np.zeros((wl["check_requests"], width), np.int32)
    seg = np.zeros((wl["check_requests"], width), np.int32)
    rows, cols, served = [], [], []
    for b, ((rid, toks), s) in enumerate(zip(pick, seqs)):
        tokens[b, :s.size] = s
        seg[b, :s.size] = 1
        p = s.size - len(toks)
        rows += [b] * len(toks)
        cols += list(range(p - 1, s.size - 1))
        served += list(toks)
    n = len(served)
    pad = -(-n // 512) * 512 - n
    rows = np.asarray(rows + [0] * pad)
    cols = np.asarray(cols + [0] * pad)
    logits = ref.served_logits(cfg, seed, tokens, seg, rows, cols, "f32")[:n]
    served = np.asarray(served)
    best = logits.max(axis=1)
    if precision == "f32":
        got = logits[np.arange(n), served]
    else:
        ctrl = ref.served_logits(cfg, seed, tokens, seg, rows, cols,
                                 precision)[:n]
        got = logits[np.arange(n), ctrl.argmax(axis=1)]
    gap = best - got
    return {"ok": True, "served_gap_max": float(gap.max()),
            "requests": len(pick), "served_tokens": int(len(served)),
            "longest": int(max(s.size for s in seqs))}


def window(cell: dict, seed: int, seconds: float, t_start: float | None = None,
           tracer=None, hooks=None):
    """Build the engine from the seed, warm it up and serve one window.

    Returns (requests, host records, {request id: tokens} finished,
    requests failed, set-up seconds since ``t_start``).  The engine and its
    weights are freed on return.
    """
    from repro.serving import StreamingEngine

    wl = cell["workload"]
    api, params = model.build(cell["config"], seed)
    eng = StreamingEngine(api, params, n_slots=wl["slots"], chunk=wl["chunk"])
    if hooks and "engine" in hooks:
        hooks["engine"](eng)
    eng.warmup()
    reqs = requests_for(cell, seed, seconds)
    setup_s = None if t_start is None else time.perf_counter() - t_start
    if tracer is not None:
        tracer.arm(seconds)
    rec = drive(eng, reqs, seconds, tracer)
    finished = {rid: list(t) for rid, t in eng.finished.items()}
    failed = len(eng.errors) + eng.n_shed
    del eng, params, api
    gc.collect()
    return reqs, rec, finished, failed, setup_s


def run(cell: dict, seed: int, seconds: float, devs: list, t_start: float,
        tracer=None, hooks=None) -> dict:
    from lib.cell import reference

    reqs, rec, finished, failed, setup_s = window(
        cell, seed, seconds, t_start, tracer, hooks)
    summary = summarize(rec, seconds)
    # The peak is a high-water mark: read before the reference runs.
    summary["memory_peak_bytes"] = device.memory_peak(devs)
    t = time.perf_counter()
    verdict = check(cell, seed, reqs, rec, finished,
                    reference(cell["config"]))
    verdict["reference_s"] = time.perf_counter() - t
    compared = {}
    if verdict["ok"]:
        compared["served_gap_max"] = (
            verdict["served_gap_max"],
            cell["workload"]["limits"]["served_gap_max"])
    summary.update(setup_s=setup_s, failed=failed, verdict=verdict,
                   compared=compared,
                   correct=bool(verdict["ok"] and all(
                       v <= lim for v, lim in compared.values())))
    return summary
