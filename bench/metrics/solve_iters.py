"""Mean propagation sweeps per commit in the window (StreamStats.iterations)."""


def read(ctx):
    its = [st.iterations for st in ctx["commit_stats"] if st.backend != "none"]
    return sum(its) / len(its) if its else None
