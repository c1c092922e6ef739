"""How much of a step's period a set of the program's spans covers
(shared by the readers of the host's own time; not a metric itself).
Spans nest and may reach across a period's edges, so what counts is the
union of their intervals clipped to the period."""
import bisect

from benchmark import reduce


def interval(span) -> tuple:
    """A span row's (start, end) in microseconds."""
    return span["ts_us"], span["ts_us"] + span["dur_us"]


def leaves(spans) -> list:
    """The spans that hold no other span. Spans of one process nest
    strictly, so in the order of (process, start, depth) a span holds
    another exactly where the next one is deeper."""
    rows = sorted(spans, key=lambda s: (s.get("pid", 0), s["ts_us"],
                                        s["depth"]))
    return [s for s, nxt in zip(rows, rows[1:] + [None])
            if nxt is None or nxt.get("pid", 0) != s.get("pid", 0)
            or nxt["depth"] <= s["depth"]]


class Cover:
    """The union of the intervals (start, end) of some spans, asked how
    many microseconds of a period [a, b) it covers."""

    def __init__(self, intervals):
        self.iv = reduce._union(intervals)      # disjoint, in order
        self.ends = [b for _, b in self.iv]

    def us(self, a, b) -> float:
        total = 0
        for x, y in self.iv[bisect.bisect_right(self.ends, a):]:
            if x >= b:
                break
            total += min(y, b) - max(x, a)
        return total
