"""KV page pool: the share of the pool's page-steps that lanes held
reserved and had not mapped, over the window's decode steps (the
engine's ``kv.reserved_page_steps`` over ``decode.steps`` times the
pool's pages)."""


def read(run):
    reserved = run.counters.get("kv.reserved_page_steps")
    steps = run.counters.get("decode.steps")
    if reserved is None or not steps:
        return None
    return 100.0 * reserved / (steps * run.arch.serving["n_pages"])
