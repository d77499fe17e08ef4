"""Open-loop serving: independent users, Poisson arrivals at a fixed
rate, unique prompts. Requests are sent on their schedule whether or not
earlier ones finished, and each is timed from its scheduled send."""
from __future__ import annotations

from chipbench import serve, traffic


class OpenLoop:
    def __init__(self, tf: dict, seconds: float, seed: int, vocab: int):
        self.warm_s = float(tf["warm_s"])
        self.sched = traffic.open_schedule(tf, seconds, seed, vocab)
        self.i = 0

    def setup(self, drv):
        pass

    def start(self, drv, t_zero):
        pass

    def arrive(self, drv, t):
        while self.i < len(self.sched) and self.sched[self.i]["sched"] <= t:
            s = self.sched[self.i]
            rec = serve.Rec(s["phase"], s["sched"], s["prompt"],
                            s["max_new"])
            drv.submit(rec, t)
            rec.sched = drv.t_zero + s["sched"]
            self.i += 1

    def next_due(self):
        if self.i < len(self.sched):
            return self.sched[self.i]["sched"]
        return None

    def on_done(self, drv, rec, t):
        pass

    def window_served(self, drv):
        return all(r.times for r in drv.by_rid.values()
                   if r.phase == "window")

    def release(self):
        self.sched = None


def run(ctx):
    loop = OpenLoop(ctx["tf"], ctx["seconds"], ctx["seed"],
                    ctx["m"]["vocab"])
    return serve.run(ctx, loop)
