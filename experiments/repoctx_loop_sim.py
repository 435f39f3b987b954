"""The scheduler's loop of ``mellum2-12b-a2.5b-L8.serve-repoctx`` on paper (PR 59): how
far ``serve_tokens_per_s`` spreads from seed to seed at GIVEN chunk and decode-step times,
before chip time is spent on six seeds. No device, no model: ``closed_loop.generate``'s own
request lists for the cell's traffic block, ``ServingEngine._step``'s order (free slots are
handed out, at most ``chunks_per_step`` chunks advance round-robin over the prefilling
slots, then one decode step of every active slot), ``_segments``'s cut of a prompt, and the
metric's own count (prompt + generated tokens of the requests that complete inside the
window). What it cannot know is the times, which are the chip's:

    python3 experiments/repoctx_loop_sim.py --decode-ms 31.5 --chunk-ms 40.9,29.9,23.0,19.2 \\
        --walk-ms 0.70,0.31,0.16,0.08 --seeds 60

``--chunk-ms``: a chunk of 2,048 / 1,024 / 512 / 256 rows at position 0; ``--walk-ms``: what
1,024 positions of prefix add to each. Prints the median, the spread (distance between the
quartiles over the median, as the driver takes it) over all seeds and over sets of six, the
completions a window and the share of steps' slots that were decoding."""
import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = os.path.join(ROOT, "chipbench", "workloads", "mellum2-12b-a2.5b-L8.serve-repoctx.json")


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return 100.0 * (q3 - q1) / statistics.median(values)


def segments(n, chunk, least):
    cuts = [(p, chunk) for p in range(0, n - chunk + 1, chunk)]
    rest = n - len(cuts) * chunk
    if rest:
        cuts.append((len(cuts) * chunk, min(max(least, 1 << (rest - 1).bit_length()), chunk)))
    return cuts


def one_run(cell, seed, seconds, chunk_ms, walk_ms, decode_ms, host_ms):
    from chipbench.traffic import closed_loop

    dep, serving, traffic = cell["deployment"], cell["serving"], cell["traffic"]
    made = closed_loop.generate(traffic, seed=seed, seconds=seconds, vocab_size=2,
                                n_slots=dep["n_slots"])
    reqs = [(len(r["prompt"]), r["max_new_tokens"]) for r in made["requests"]]
    t0, t1 = made["window"]
    chunk, per_step = (serving["chunked_prefill"][k] for k in ("chunk_size", "chunks_per_step"))
    cost = lambda start, width: (chunk_ms[width] + walk_ms[width] * start / 1024.0)  # noqa: E731
    queue, nxt = list(range(made["clients"])), made["clients"]
    free = list(range(dep["n_slots"]))
    prefilling, active = {}, {}  # slot -> [request, segments left] / [request, tokens left]
    now, rr, tokens, done, busy, steps = 0.0, 0, 0, 0, 0, 0
    while now < t1 + traffic["grace_s"] and (queue or prefilling or active):
        while free and queue:
            i = queue.pop(0)
            prefilling[free.pop(0)] = [i, segments(reqs[i][0], chunk, serving["min_prefill_bucket"])]
        step = host_ms
        for _ in range(per_step):
            if not prefilling:
                break
            slots = sorted(prefilling)
            slot = slots[rr % len(slots)]
            rr += 1
            i, segs = prefilling[slot]
            step += cost(*segs.pop(0))
            if not segs:  # the last chunk gives the first token
                del prefilling[slot]
                active[slot] = [i, reqs[i][1] - 1]
        if active:
            step += decode_ms
        now += step / 1e3
        if t0 <= now < t1:
            busy, steps = busy + len(active), steps + 1
        for slot in sorted(active):
            i, left = active[slot]
            active[slot][1] = left - 1
            if left - 1 <= 0:
                del active[slot]
                free.append(slot)
                if t0 <= now < t1:
                    tokens, done = tokens + sum(reqs[i]), done + 1
                if nxt < len(reqs):  # the client's next request, at once
                    queue.append(nxt)
                    nxt += 1
    return tokens / (t1 - t0), done, 100.0 * busy / max(steps, 1) / dep["n_slots"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=60)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--decode-ms", type=float, required=True)
    ap.add_argument("--chunk-ms", required=True)
    ap.add_argument("--walk-ms", required=True)
    ap.add_argument("--host-ms", type=float, default=1.0)
    ap.add_argument("--chunks-per-step", type=int)
    args = ap.parse_args()
    with open(CELL) as f:
        cell = json.load(f)
    if args.chunks_per_step:
        cell["serving"]["chunked_prefill"]["chunks_per_step"] = args.chunks_per_step
    widths = (2048, 1024, 512, 256)
    chunk_ms = dict(zip(widths, map(float, args.chunk_ms.split(","))))
    walk_ms = dict(zip(widths, map(float, args.walk_ms.split(","))))
    runs = [one_run(cell, 1000003 * s + 11, args.seconds, chunk_ms, walk_ms, args.decode_ms,
                    args.host_ms) for s in range(args.seeds)]
    rates = [r[0] for r in runs]
    sixes = [spread(rates[i:i + 6]) for i in range(0, len(rates) - 5, 6)]
    print(json.dumps({
        "seeds": args.seeds, "median_tokens_per_s": statistics.median(rates),
        "spread_pct": spread(rates), "spread_pct_sets_of_six": [round(s, 2) for s in sixes],
        "completions_median": statistics.median(r[1] for r in runs),
        "slot_occupancy_mean": statistics.mean(r[2] for r in runs)}))


if __name__ == "__main__":
    main()
