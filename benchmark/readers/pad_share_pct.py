"""Share of the forest step's padded block rows that hold no block,
over the window's records; nothing where the records carry no
``pad_blocks`` (a uniform run, or a program from before schema 13)."""


def read(ctx):
    rows = [(r["n_blocks"], r["pad_blocks"]) for r in ctx["window"]["records"]
            if r.get("n_blocks") and r.get("pad_blocks")]
    if not rows:
        return None
    return 100.0 * (1.0 - sum(n for n, _ in rows) / sum(p for _, p in rows))
