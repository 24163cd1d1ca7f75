"""Mean over the window's steps of KV pages in use over the pool, in
percent (the page allocator's count after each step)."""
from sartbench.stats import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return 100.0 * sum(s.used_pages for s in steps) / len(steps) \
        / run.num_pages
