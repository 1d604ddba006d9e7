"""Find an open-loop cell's knee, once, on the chip::

    python3 benchmark/rate_sweep.py --workload <cell> --rates 4,5,6,7 --seconds 40

One process builds the cell's system, then offers each rate in turn for
``--seconds`` (the mix's own lengths, preload sized to the rate) and prints,
for each, whether the backlog grew: the requests waiting for a slot at one
third, two thirds and the end of the window, slot use, and the latencies. The
knee is the highest rate whose backlog does not grow; the cell's rate, written
into its traffic file as a number, is four fifths of it. Like ``run.py`` this
runs on a TPU or not at all.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Watch:
    """The hooks of a window, keeping only the backlog and slot use."""

    def __init__(self, served):
        self.served, self.waiting, self.slots, self.open = served, [], [], False

    def window_open(self):
        self.open = True

    def window_close(self):
        self.open = False

    def tick(self, now):
        if self.open:
            b = self.served.batcher
            self.waiting.append((now, len(b._waiting)))
            self.slots.append(len(b._slot_to_sid))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import latency
    from benchmark.cell import load_cell
    from benchmark.run import configure_jax
    from benchmark.serving import Served, build_batcher, preload, warm_up
    from benchmark.weights import make_weights

    cell = load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("rate_sweep: needs a TPU", file=sys.stderr)
        return 2
    configure_jax()
    kind, config = cell.kind, cell.config
    weights = make_weights(cell.model, args.seed, config["torch_dtype"])
    served = Served(build_batcher(config, weights))
    warm_up(served, cell.traffic, config["vocab_size"])
    mean_answer = float(np.dot(cell.traffic["answer"]["values"],
                               cell.traffic["answer"]["weights"])
                        / np.sum(cell.traffic["answer"]["weights"]))
    gap_s = 0.5
    for rate in [float(r) for r in args.rates.split(",")]:
        slots = config["serving"]["max_slots"]
        inflight = int(min(slots - 8, round(rate * mean_answer * gap_s)))
        traffic = dict(cell.traffic, rate=rate, inflight_at_open=inflight)
        plan = kind.generate(traffic, config, args.seed, args.seconds)
        preload(served, plan)
        watch = Watch(served)
        window = kind.drive(served, plan, traffic, args.seconds, watch)
        rec = {"requests": window["requests"], "preload": window["preload"],
               "t0": window["t0"], "t1": window["t1"]}
        gaps = latency.gaps_ms(rec)
        gap_s = (latency.mean(gaps) or 500.0) / 1e3
        ttft = latency.ttfts_ms(rec)
        w = watch.waiting
        at = lambda f: w[min(len(w) - 1, int(f * len(w)))][1]  # noqa: E731
        print(json.dumps({
            "rate": rate, "inflight_at_open": inflight,
            "due_in_window": window["attempted"],
            "waiting_at_third_twothirds_end": [at(1 / 3), at(2 / 3), at(1.0)],
            "waiting_max": max(x for _, x in w),
            "slots_mean": float(np.mean(watch.slots)),
            "slots_max": int(max(watch.slots)),
            "ttft_mean_ms": latency.mean(ttft),
            "ttft_p90_ms": latency.pct(ttft, 90),
            "gap_mean_ms": latency.mean(gaps),
            "late_p90_ms": latency.pct(latency.late_ms(rec), 90),
        }), flush=True)
        for sid in list(served.live):   # next rate starts from an empty engine
            served.batcher.discard(sid)
        served.live.clear()
        served.done.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
