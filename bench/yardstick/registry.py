"""Window deltas of the program's own counters and histograms, read
from two `repro.obs` registry snapshots (before and after the
window)."""
from __future__ import annotations


def _split(series: str):
    name, _, rest = series.partition("{")
    return name, rest


class Delta:
    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after

    def _match(self, kind, name, labels):
        for series, val in self.after.get(kind, {}).items():
            nm, rest = _split(series)
            if nm == name and all(f'{k}="{v}"' in rest
                                  for k, v in labels.items()):
                yield series, val, self.before.get(kind, {}).get(series)

    def counter(self, name: str, **labels) -> float:
        return sum(v - (b or 0.0)
                   for _, v, b in self._match("counters", name, labels))

    def hist(self, name: str, **labels) -> tuple[int, float]:
        """(count, sum) observed in the window over the matching label
        sets."""
        count, total = 0, 0.0
        for _, h, b in self._match("histograms", name, labels):
            count += h["count"] - (b["count"] if b else 0)
            total += h["sum"] - (b["sum"] if b else 0.0)
        return count, total

    def hist_mean(self, name: str, **labels):
        count, total = self.hist(name, **labels)
        return total / count if count else None
