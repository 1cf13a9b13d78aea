"""Share of the window's decisions, in %, that ran no MCMC fit of their
own (service.pool.hit over hit + miss): they adopted a sibling's draws or
reused their cached ones."""


def read(run):
    hit = run.counters.get("service.pool.hit", 0)
    miss = run.counters.get("service.pool.miss", 0)
    return 100.0 * hit / (hit + miss) if hit + miss else None
