"""Closed-loop document QA: a few long documents prefilled into the
prefix cache during set-up, then clients that each send a document (by
Zipf) plus a unique question, and send the next the moment an answer
ends (no think time). Each request is timed from its send."""
from __future__ import annotations

from chipbench import harness, serve, traffic


class DocLoop:
    def __init__(self, tf: dict, seconds: float, seed: int, vocab: int):
        self.warm_s = float(tf["warm_s"])
        self.horizon = self.warm_s + seconds
        self.clients = int(tf["clients"])
        self.mix = traffic.docqa(tf, seed, vocab, int(tf["blocks"]))
        self.i = 0
        self.ready = []       # (send time relative to t_zero, client)

    def setup(self, drv):
        """Prefill every document into the prefix cache (one request of
        one new token each) and wait until all have retired."""
        for doc in self.mix["documents"]:
            drv.submit(serve.Rec("setup", 0.0, doc, 1), 0.0)
        while not drv.eng.idle:
            drv.step()
        hits = drv.eng.pkv.stats()
        harness.log(f"documents prefilled: {len(self.mix['documents'])}, "
                    f"{hits.get('prefix_nodes', 0)} cached pages")
        drv.by_rid.clear()
        drv.steps.clear()
        drv.finished.clear()

    def start(self, drv, t_zero):
        self.ready = [(0.0, c) for c in range(self.clients)]

    def _send(self, drv, t, client):
        if self.i >= len(self.mix["requests"]):
            return
        r = self.mix["requests"][self.i]
        self.i += 1
        phase = ("warm" if t < self.warm_s else
                 "window" if t < self.horizon else "drain")
        rec = serve.Rec(phase, drv.t_zero + t, r["prompt"], r["max_new"],
                        client=client)
        drv.submit(rec, t)

    def arrive(self, drv, t):
        due = [x for x in self.ready if x[0] <= t]
        self.ready = [x for x in self.ready if x[0] > t]
        for when, client in due:
            self._send(drv, when, client)

    def next_due(self):
        return min((x[0] for x in self.ready), default=None)

    def on_done(self, drv, rec, t):
        self.ready.append((t, rec.client))

    def window_served(self, drv):
        return all(r.times for r in drv.by_rid.values()
                   if r.phase == "window")

    def release(self):
        self.mix = None


def run(ctx):
    loop = DocLoop(ctx["tf"], ctx["seconds"], ctx["seed"], ctx["m"]["vocab"])
    return serve.run(ctx, loop)
