"""Count XLA executables compiled during the canonical init climb.

Init on the canonical case is dominated by compiles, and every
distinct executable costs at least a cache load and a dispatch even
when warm. The number of distinct executables the levelMax climb
creates is therefore a code property worth measuring and shrinking.

Uses jax_log_compiles: every cache-miss compile (in-process; a
persistent-cache load is not a miss) logs one line. Reports
counts per jitted-function name for (a) the climb (initialize()), and
(b) 3 production steps + 1 regrid afterwards, so climb-only
executables are visible.

    python -m validation.init_compiles [--levelmax 8]
"""

from __future__ import annotations

import argparse
import json
import logging
import re
import time


class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.events: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        m = re.search(r"Finished XLA compilation of (?:jit\()?"
                      r"([\w.<>\[\]_-]+)", msg)
        if m:
            self.events.append(m.group(1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--levelmax", type=int, default=8)
    args = ap.parse_args()

    import jax

    from cup2d_tpu.cache import enable_compilation_cache
    enable_compilation_cache()
    from validation.canonical import build_canonical_sim

    jax.config.update("jax_log_compiles", True)
    counter = _CompileCounter()
    logging.getLogger("jax._src.interpreters.pxla").addHandler(counter)
    logging.getLogger("jax._src.interpreters.pxla").setLevel(logging.DEBUG)
    logging.getLogger("jax._src.dispatch").addHandler(counter)
    logging.getLogger("jax._src.dispatch").setLevel(logging.DEBUG)

    sim = build_canonical_sim(levelmax=args.levelmax)
    t0 = time.perf_counter()
    sim.initialize()
    init_s = time.perf_counter() - t0
    init_events = list(counter.events)
    counter.events.clear()

    t1 = time.perf_counter()
    for _ in range(3):
        sim.step_once()
    sim.adapt()
    sim.step_once()
    post_s = time.perf_counter() - t1
    post_events = list(counter.events)

    def by_name(evs):
        out: dict[str, int] = {}
        for e in evs:
            out[e] = out.get(e, 0) + 1
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    print(json.dumps({
        "levelmax": args.levelmax,
        "init_s": round(init_s, 1),
        "init_compiles": len(init_events),
        "init_by_name": by_name(init_events),
        "post_s": round(post_s, 1),
        "post_compiles": len(post_events),
        "post_by_name": by_name(post_events),
        "n_blocks": len(sim.forest.blocks),
        "n_pad": int(sim._npad_hwm),
    }))


if __name__ == "__main__":
    main()
