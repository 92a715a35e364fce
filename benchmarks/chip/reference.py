"""The plain reference: exact order statistics of what was acknowledged.

Independent of the program.  It knows the bodies the benchmark generated
(their float32 values, as the server parses them from the POST text), how
often each POST was acknowledged, and -- from the window proxy's state log
-- which slice each acknowledged POST landed in.  From that it rebuilds,
for any window version, the multiset of values behind every per-key,
windowed and fleet answer, and holds each answer to DDSketch's guarantee:

    |answer - x_r| <= (alpha_L + rounding) * |x_r|,   r = floor(q * (n - 1))

where ``x_r`` is the exact rank-``r`` value, ``alpha_L`` the relative
accuracy after ``L`` uniform collapses (``gamma_L = gamma ** 2 ** L``) at
the row's level, and ``rounding`` the 1e-5 by which float32 keying of a
value within ~1e-6 of a bucket edge can move it one bucket over.  The
device computes the rank in float32 (``float32(q) * float32(n - 1)``), and
past ``2**22`` values its float32 count cumsum places a rank within
``n / 2**22`` of the exact one, so the answer is held to the nearest of
``x_lo .. x_hi``, ``lo = min(r, r32) - d``, ``hi = max(r, r32) + d`` with
``r32`` the float32 rank and ``d = floor(n / 2**22)``.
"""

from __future__ import annotations

import math

import numpy as np

ROUNDING = 1e-5


def alpha_at(alpha: float, level: int) -> float:
    g = ((1.0 + alpha) / (1.0 - alpha)) ** (1 << int(level))
    return (g - 1.0) / (g + 1.0)


class Bodies:
    """Every body a run can ingest: key name, float32 values, and an index
    from the batch identity the proxy logs to the body."""

    def __init__(self):
        self.keys: list[str] = []
        self.values: list[np.ndarray] = []
        self.index: dict[tuple, int] = {}

    def add(self, key: str, values) -> int:
        v = np.asarray(values, np.float32).reshape(-1)
        i = len(self.keys)
        self.keys.append(key)
        self.values.append(v)
        n = v.size
        self.index.setdefault((key, n, float(v[0]), float(v[n // 2]), float(v[-1])), i)
        return i

    def __len__(self) -> int:
        return len(self.keys)


class State:
    """Slice contents (count of each body) at every version of the log."""

    def __init__(self, bodies: Bodies, log: list[tuple]):
        self.nb = nb = len(bodies)
        self.unknown = 0  # logged batches that match no generated body
        self.gaps = 0  # versions the log skipped (a state change not logged)
        self.sealed: list[np.ndarray] = []  # count vector of each sealed slice
        self._ticks: list[np.ndarray] = []  # body ids of each ingest tick
        # version -> (sealed slices so far, first and end tick of the live slice)
        self._at: dict[int, tuple[int, int, int]] = {}
        first, last_v = 0, None
        for kind, version, _t0, _t1, ids in log:
            if kind == "ingest":
                if not ids:
                    continue
                got = [bodies.index.get(bid) for bid in ids]
                self.unknown += sum(i is None for i in got)
                self._ticks.append(np.asarray([i for i in got if i is not None],
                                              np.int64))
            elif kind == "seal":
                self.sealed.append(self._live(first, len(self._ticks)))
                first = len(self._ticks)
            elif kind == "reset":
                first = len(self._ticks)
            if last_v is not None and version != last_v + 1:
                self.gaps += 1
            last_v = version
            self._at[version] = (len(self.sealed), first, len(self._ticks))
        self.last_version = last_v
        every = self._ticks or [np.zeros(0, np.int64)]
        self.ingested = np.bincount(np.concatenate(every), minlength=nb)

    def _live(self, a: int, b: int) -> np.ndarray:
        ids = self._ticks[a:b]
        if not ids:
            return np.zeros(self.nb, np.int64)
        return np.bincount(np.concatenate(ids), minlength=self.nb)

    def window(self, version: int, slices: int | None) -> np.ndarray:
        """Body counts behind an answer at ``version``: the live slice, plus
        the last ``slices - 1`` sealed slices for a windowed read."""
        n_sealed, a, b = self._at[version]
        out = self._live(a, b).astype(np.float64)
        if slices:
            for s in self.sealed[max(0, n_sealed - (slices - 1)):n_sealed]:
                out = out + s
        return out


class Exact:
    """Exact weighted order statistics per key and over the fleet."""

    def __init__(self, bodies: Bodies, key_names: list[str]):
        self.key_id = {k: i for i, k in enumerate(key_names)}
        body_key = np.asarray([self.key_id[k] for k in bodies.keys], np.int64)
        sizes = np.asarray([v.size for v in bodies.values], np.int64)
        vals = np.concatenate(bodies.values).astype(np.float64)
        owner = np.repeat(np.arange(len(bodies)), sizes)
        lane_key = body_key[owner]
        by_key = np.lexsort((vals, lane_key))
        self.k_vals, self.k_owner = vals[by_key], owner[by_key]
        self.k_start = np.searchsorted(lane_key[by_key], np.arange(len(key_names) + 1))
        fleet = np.argsort(vals, kind="stable")
        self.f_vals, self.f_owner = vals[fleet], owner[fleet]

    @staticmethod
    def _pick(vals, cum, base, n, qs):
        """(len(qs), 2) lowest and highest value within the rank slack of
        each q's exact rank (NaN where there is nothing)."""
        out = np.full((len(qs), 2), np.nan)
        if n <= 0:
            return out
        d = math.floor(n / 2**22)
        for j, q in enumerate(qs):
            r = math.floor(q * (n - 1))
            r32 = math.floor(np.float32(q) * np.float32(n - 1))  # the device's rank
            lo, hi = max(0, min(r, r32) - d), min(int(n) - 1, max(r, r32) + d)
            for c, rr in enumerate((lo, hi)):
                out[j, c] = vals[np.searchsorted(cum, base + rr, side="right")]
        return out

    def per_key(self, counts: np.ndarray, keys: list[str], qs) -> dict[str, np.ndarray]:
        out = {}
        if len(keys) > 64:  # one pass over every key's values
            cum = np.cumsum(counts[self.k_owner])
            for k in keys:
                i = self.key_id[k]
                a, b = self.k_start[i], self.k_start[i + 1]
                base = cum[a - 1] if a > 0 else 0.0
                n = (cum[b - 1] - base) if b > a else 0.0
                out[k] = self._pick(self.k_vals, cum, base, n, qs)
            return out
        for k in keys:  # a few keys: their own values only
            i = self.key_id[k]
            a, b = self.k_start[i], self.k_start[i + 1]
            cum = np.cumsum(counts[self.k_owner[a:b]])
            out[k] = self._pick(self.k_vals[a:b], cum, 0.0, cum[-1] if b > a else 0.0, qs)
        return out

    def fleet(self, counts: np.ndarray, qs) -> np.ndarray:
        cum = np.cumsum(counts[self.f_owner])
        return self._pick(self.f_vals, cum, 0.0, cum[-1] if cum.size else 0.0, qs)


class Checker:
    """Answers against the exact values; keeps the worst ratio and faults."""

    def __init__(self, alpha: float):
        self.alpha = alpha
        self.worst = 0.0  # max |err| / ((alpha_L + ROUNDING) |exact|)
        self.out_of_bound = 0  # answers past the guarantee
        self.empty_mismatch = 0  # an answer where there is nothing, or none
        self.checked = 0
        self.first_bad: list[str] = []

    def check(self, what: str, got, exact: np.ndarray, level: int) -> None:
        """``exact`` is ``Exact._pick``'s (len(qs), 2) value range per q."""
        bound = alpha_at(self.alpha, level) + ROUNDING
        for q_i, (g, (lo, hi)) in enumerate(zip(got, exact.tolist())):
            x = lo
            empty_g = g is None or (isinstance(g, float) and math.isnan(g))
            if math.isnan(x) or empty_g:
                if math.isnan(x) != empty_g:
                    self.empty_mismatch += 1
                    if len(self.first_bad) < 8:
                        self.first_bad.append(f"{what}[{q_i}]: {g!r} vs exact {x!r}")
                continue
            x = min(max(g, lo), hi)  # the nearest value within the rank slack
            ratio = abs(g - x) / (bound * abs(x))
            self.checked += 1
            if ratio > self.worst:
                self.worst = ratio
            self.out_of_bound += ratio > 1.0
            if ratio > 1.0 and len(self.first_bad) < 8:
                self.first_bad.append(f"{what}[{q_i}]: {g!r} vs exact {x!r}"
                                      f" ({ratio:.4f} of the bound, level {level})")
