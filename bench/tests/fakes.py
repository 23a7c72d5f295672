"""A stand-in engine on a fake clock, for testing the driver and the
window arithmetic without a model."""
from types import SimpleNamespace


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-4)


class FakeEngine:
    """FIFO, ``max_batch`` rows; each step lasts ``dt`` seconds plus what
    ``stall(now)`` returns, and gives every running request one token."""

    def __init__(self, clock, max_batch=4, dt=0.01, stall=lambda i: 0.0):
        self.clock, self.max_batch, self.dt, self.stall = clock, max_batch, dt, stall
        self.steps = self.decode_tokens = self.prefill_tokens = 0
        self.trace_counts = {}
        self.waiting, self.running = [], []
        self.sched = SimpleNamespace(has_work=False, n_preemptions=0)
        self.alloc = SimpleNamespace(n_blocks=64, n_available=64)

    def submit(self, req):
        req.state = "waiting"
        self.waiting.append(req)
        self.sched.has_work = True

    def step(self):
        self.clock.t += self.dt + self.stall(self.clock.t)
        while self.waiting and len(self.running) < self.max_batch:
            r = self.waiting.pop(0)
            r.admitted_time = self.clock.t
            r.prefilled = len(r.tokens)
            self.running.append(r)
        for r in list(self.running):
            r.output.append(1)
            self.decode_tokens += 1
            if len(r.output) >= r.max_new_tokens:
                r.state = "finished"
                r.finish_time = self.clock.t
                self.running.remove(r)
            else:
                r.state = "running"
        self.steps += 1
        self.sched.has_work = bool(self.waiting or self.running)
