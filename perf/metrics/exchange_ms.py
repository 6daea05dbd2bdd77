"""Exchange layer (the t2 all-to-all, ``parallel/exchange.py``): device
time in collective operations per traffic step, on the chip that spends
the most. None where no collective ran."""


def read(run):
    t = run.trace
    if t is None or not run.steps or not t.ops:
        return None
    worst = max(t.collective_s(d) for d in t.ops)
    return 1e3 * worst / run.steps if worst > 0 else None
