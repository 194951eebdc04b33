"""Plain reference of the read mapper for the ``mapper`` configurations.

Imports nothing of the program; it works from the reference genome and
the reads that the benchmark made. Per read, as the configuration states
it (minimap2's skeleton as the Squire paper uses it):

  seed   window minimizers ((w, k) = the file's, 2-bit k-mer codes hashed
         with the Murmur3 finalizer, leftmost minimum, consecutive
         repeats dropped); each looked up in the genome's minimizer index
         (hash-sorted, up to ``max_occ`` hits in genome order); anchors
         (read pos, genome pos) sorted by genome position, stable.
  chain  f(i) = max(k, max over the ``band`` previous anchors j of
         f(j) + min(dq, dr, k) - (0.01 k gap + 0.5 log2(gap + 1))),
         for 0 < dq, 0 <= dr, both <= 5000, gap = |dq - dr| <= 500;
         best predecessor by the first maximum. The match-up scores are
         one vectorized float32 pass; the recurrence runs in float32 on
         the host. Backtrack: chains from the highest f down, each anchor
         used once, at least 2 anchors and score >= min_chain_score.
  align  Smith-Waterman (match 2, mismatch -4, linear gap 4, floor 0) of
         the read against the genome window the best chain spans, padded
         by ``sw_window_pad``; the score is the matrix maximum. The
         matrix is swept by anti-diagonals in exact integers (the
         configuration's float32 holds every score exactly).

``map`` does one read on the host. ``map_all`` seeds and chains each read
on the host the same way, then aligns all of them together: one jitted
anti-diagonal sweep on the device, batched over reads (``sw_all``).

``dtype`` takes the control's precision: bfloat16 rounds every match-up
score, chain score and SW cell.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

NEG = np.float32(-1e18)
# Padding codes of the batched SW sweep: a read is padded with one, its
# window with the other; neither matches a base (0-3) or the other.
PAD_READ, PAD_WINDOW = 254, 255
SW_GRID = 512          # padded read and window lengths are multiples
SW_MAX_BATCH = 512     # reads per call of the sweep


@dataclasses.dataclass
class Mapping:
    pos: int
    sw_score: float
    chain_score: float
    n_anchors: int


def hash32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
    x ^= x >> np.uint32(13)
    x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
    x ^= x >> np.uint32(16)
    return x


def minimizers(seq: np.ndarray, k: int, w: int):
    """(positions, hashes) of the window minimizers of ``seq``."""
    seq = np.asarray(seq, np.uint32)
    nk = len(seq) - k + 1
    code = np.zeros(nk, np.uint32)
    for t in range(k):
        code = (code << np.uint32(2)) | seq[t:t + nk]
    h = hash32(code)
    nw = nk - w + 1
    view = np.lib.stride_tricks.sliding_window_view(h, w)[:nw]
    pos = view.argmin(axis=1) + np.arange(nw)
    keep = np.concatenate([[True], pos[1:] != pos[:-1]])
    return pos[keep], h[pos[keep]]


class Index:
    def __init__(self, genome: np.ndarray, k: int, w: int):
        pos, h = minimizers(genome, k, w)
        order = np.argsort(h, kind="stable")
        self.hashes = h[order]
        self.positions = pos[order].astype(np.int64)


class Reference:
    def __init__(self, genome: np.ndarray, cfg: dict, dtype=np.float32):
        self.genome = np.asarray(genome, np.int8)
        self.cfg = cfg
        self.dtype = dtype
        self.index = Index(self.genome, cfg["k"], cfg["w"])

    # -- seed ------------------------------------------------------------

    def anchors(self, read: np.ndarray):
        c = self.cfg
        qpos, qh = minimizers(read, c["k"], c["w"])
        lo = np.searchsorted(self.index.hashes, qh, side="left")
        hi = np.searchsorted(self.index.hashes, qh, side="right")
        q, r = [], []
        for p, a, b in zip(qpos, lo, hi):
            for j in range(a, min(b, a + c["max_occ"])):
                q.append(p)
                r.append(self.index.positions[j])
        q = np.asarray(q, np.int64)
        r = np.asarray(r, np.int64)
        order = np.argsort(r, kind="stable")
        return q[order], r[order]

    # -- chain -----------------------------------------------------------

    def chain(self, q: np.ndarray, r: np.ndarray):
        c = self.cfg
        band, kmer = c["band"], c["k"]
        n = len(q)
        s = _matchup_scores(q, r, band, kmer).astype(self.dtype)
        f = np.zeros(n, self.dtype)
        pred = np.full(n, -1, np.int64)
        ring = np.full(band, NEG, self.dtype)   # ring[t-1] = f(i - t)
        for i in range(n):
            cand = (s[i] + ring).astype(self.dtype)
            t = int(np.argmax(cand))
            best = cand[t]
            f[i] = max(best, self.dtype(kmer))
            pred[i] = i - (t + 1) if best >= kmer else -1
            ring = np.concatenate([[f[i]], ring[:-1]]).astype(self.dtype)
        return f.astype(np.float32), pred

    @staticmethod
    def backtrack(f: np.ndarray, pred: np.ndarray, min_score: float):
        order = np.argsort(-f)
        used = np.zeros(len(f), bool)
        chains = []
        for i in order:
            if f[i] < min_score:
                break
            if used[i]:
                continue
            node, members = int(i), []
            while node >= 0 and not used[node]:
                used[node] = True
                members.append(node)
                node = int(pred[node])
            if len(members) >= 2:
                chains.append((float(f[i]), members[::-1]))
        return chains

    # -- align -----------------------------------------------------------

    def sw(self, a: np.ndarray, b: np.ndarray) -> float:
        c = self.cfg
        match, mismatch, gap = c["sw_match"], c["sw_mismatch"], c["sw_gap"]
        exact = self.dtype == np.float32
        dt = np.int64 if exact else self.dtype
        n, m = len(a), len(b)
        a = np.asarray(a, np.int64)
        b = np.asarray(b, np.int64)
        # H over (n + 1) x (m + 1) anti-diagonals; row 0 and column 0 are 0
        prev2 = np.zeros(1, dt)            # diagonal d - 2, by row index
        prev = np.zeros(2, dt)             # diagonal d - 1
        best = 0
        for d in range(2, n + m + 1):
            i = np.arange(max(1, d - m), min(n, d - 1) + 1)
            j = d - i
            lo1 = max(0, d - 1 - m)        # first row index of diag d - 1
            lo2 = max(0, d - 2 - m)
            diag = prev2[i - 1 - lo2]
            up = prev[i - 1 - lo1]
            left = prev[i - lo1]
            sub = np.where(a[i - 1] == b[j - 1], match, mismatch)
            h = np.maximum(np.maximum(diag + sub, up - gap), left - gap)
            h = np.maximum(h, 0).astype(dt)
            lo = max(0, d - m)
            cur = np.zeros(min(n, d) - lo + 1, dt)
            cur[i - lo] = h
            best = max(best, float(h.max()) if len(h) else 0.0)
            prev2, prev = prev, cur
        return float(best)

    def sw_all(self, pairs: Sequence[Tuple[np.ndarray, np.ndarray]]
               ) -> List[float]:
        """``sw`` of every (read, window) pair, swept on the device.

        Pairs are grouped by their lengths padded to ``SW_GRID``, and each
        group goes through ``_sw_sweep`` in chunks of at most
        ``SW_MAX_BATCH`` pairs, the last chunk padded to a power of two
        (at least 8) with empty pairs: a few programs, and bounded memory.
        A read is padded with ``PAD_READ`` and its window with
        ``PAD_WINDOW``, which match nothing. That leaves the score as it
        is: the true cells depend only on cells above and left of them,
        all true; a padded cell takes a mismatch or a gap from one of its
        neighbours, so it lies below the largest true cell it derives
        from, and the floor 0 is reached by true cells too. The sweep
        keeps the anti-diagonal form, so under bfloat16 every value it
        rounds is a cell, as in ``sw``.
        """
        c = self.cfg
        exact = self.dtype == np.float32
        dt = jnp.int32 if exact else jnp.dtype(self.dtype)
        cast = int if exact else float
        scoring = dict(match=cast(c["sw_match"]),
                       mismatch=cast(c["sw_mismatch"]),
                       gap=cast(c["sw_gap"]), dtype=dt)
        groups = {}
        for k, (a, b) in enumerate(pairs):
            key = (_grid(len(a)), _grid(len(b)))
            groups.setdefault(key, []).append(k)
        out = [0.0] * len(pairs)
        for (n, m), ks in sorted(groups.items()):
            for s in range(0, len(ks), SW_MAX_BATCH):
                chunk = ks[s:s + SW_MAX_BATCH]
                rows = max(8, 1 << (len(chunk) - 1).bit_length())
                a = np.full((rows, n), PAD_READ, np.int32)
                b = np.full((rows, m), PAD_WINDOW, np.int32)
                for r, k in enumerate(chunk):
                    a[r, :len(pairs[k][0])] = pairs[k][0]
                    b[r, :len(pairs[k][1])] = pairs[k][1]
                best = np.asarray(_sw_sweep(a, b, **scoring))
                for r, k in enumerate(chunk):
                    out[k] = float(best[r])
        return out

    # -- a read ----------------------------------------------------------

    def locate(self, read: np.ndarray):
        """Seed and chain ``read``: (mapping without its SW score, the
        genome window (lo, hi) to align it against, or None where the
        read maps nowhere and the mapping is final)."""
        c = self.cfg
        read = np.asarray(read)
        if len(read) < c["k"] + c["w"]:
            return Mapping(-1, 0.0, 0.0, 0), None
        q, r = self.anchors(read)
        nv = len(q)
        if nv < 2:
            return Mapping(-1, 0.0, 0.0, nv), None
        f, pred = self.chain(q, r)
        chains = self.backtrack(f, pred, c["min_chain_score"])
        if not chains:
            return Mapping(-1, 0.0, 0.0, nv), None
        score, members = chains[0]
        first, last = members[0], members[-1]
        pad = c["sw_window_pad"]
        lo = max(0, int(r[first]) - int(q[first]) - pad)
        hi = min(len(self.genome),
                 int(r[last]) + (len(read) - int(q[last])) + pad)
        if hi - lo < c["k"]:
            return Mapping(-1, 0.0, score, nv), None
        return Mapping(lo, 0.0, score, nv), (lo, hi)

    def map(self, read: np.ndarray) -> Mapping:
        out, window = self.locate(read)
        if window is not None:
            out.sw_score = self.sw(np.asarray(read),
                                   self.genome[window[0]:window[1]])
        return out

    def map_all(self, reads: Sequence[np.ndarray]) -> List[Mapping]:
        """``[self.map(r) for r in reads]``, with the alignments of all
        reads swept together on the device."""
        located = [self.locate(r) for r in reads]
        todo = [k for k, (_, w) in enumerate(located) if w is not None]
        scores = self.sw_all([(np.asarray(reads[k]),
                               self.genome[slice(*located[k][1])])
                              for k in todo])
        for k, score in zip(todo, scores):
            located[k][0].sw_score = score
        return [m for m, _ in located]


def _grid(n: int) -> int:
    return -(-max(n, 1) // SW_GRID) * SW_GRID


@functools.partial(jax.jit,
                   static_argnames=("match", "mismatch", "gap", "dtype"))
def _sw_sweep(a, b, *, match, mismatch, gap, dtype):
    """(B,) matrix maxima of the Smith-Waterman matrices of reads ``a``
    (B, N) against windows ``b`` (B, M), swept by anti-diagonals d = i + j
    with every value of ``dtype``. A diagonal is held by its row index i
    in 0..N; cells off the matrix (j < 1 or j > M) and row 0 are 0."""
    rows_n, n = a.shape
    m = b.shape[1]
    # rev[:, M + N + 1 - d + i] = b[:, d - 1 - i], PAD_WINDOW off the window
    edge = jnp.full((rows_n, n + 1), PAD_WINDOW, b.dtype)
    rev = jnp.concatenate([edge, b, edge], axis=1)[:, ::-1]
    a_i = jnp.concatenate(
        [jnp.full((rows_n, 1), PAD_READ, a.dtype), a], axis=1)
    i = jnp.arange(n + 1)
    zero = jnp.zeros((rows_n, n + 1), dtype)
    match, mismatch, gap = (jnp.asarray(v, dtype)
                            for v in (match, mismatch, gap))

    def shift(x):                     # x[:, i - 1], 0 at i = 0
        return jnp.concatenate([zero[:, :1], x[:, :-1]], axis=1)

    def diagonal(d, carry):
        prev2, prev, best = carry     # diagonals d - 2 and d - 1
        b_j = jax.lax.dynamic_slice_in_dim(rev, m + n + 1 - d, n + 1, axis=1)
        sub = jnp.where(a_i == b_j, match, mismatch)
        h = jnp.maximum(jnp.maximum(shift(prev2) + sub, shift(prev) - gap),
                        prev - gap)
        j = d - i
        on = (i >= 1) & (j >= 1) & (j <= m)
        h = jnp.where(on, jnp.maximum(h, zero), zero)
        return prev, h, jnp.maximum(best, h)

    _, _, best = jax.lax.fori_loop(2, n + m + 1, diagonal,
                                   (zero, zero, zero))
    return best.max(axis=1)


@jax.jit
def _scores_f32(q, r, band_arange, kmer):
    n = q.shape[0]
    j = jnp.arange(n)[:, None] - band_arange[None, :]
    ok = j >= 0
    jc = jnp.clip(j, 0, n - 1)
    dq = q[:, None] - q[jc]
    dr = r[:, None] - r[jc]
    gap = jnp.abs(dq - dr).astype(jnp.float32)
    alpha = jnp.minimum(jnp.minimum(dq, dr), kmer).astype(jnp.float32)
    beta = 0.15000000000000002 * gap + 0.5 * jnp.log2(gap + 1.0)
    ok = (ok & (dq > 0) & (dr >= 0) & (dq <= 5000) & (dr <= 5000)
          & (gap <= 500))
    return jnp.where(ok, alpha - beta, NEG)


def _matchup_scores(q: np.ndarray, r: np.ndarray, band: int, kmer: int
                    ) -> np.ndarray:
    """(n, band) float32 scores S[i, t] of chaining anchor i after
    anchor i - t (t = 1..band); -1e18 where not allowed. Anchors are
    padded on the host to a multiple of 512, so that one program serves
    every read of a padded size."""
    n = len(q)
    npad = -(-max(n, 1) // 512) * 512
    qp = np.zeros(npad, np.int32)
    rp = np.full(npad, 2**30, np.int32)
    qp[:n], rp[:n] = q, r
    s = _scores_f32(qp, rp, np.arange(1, band + 1, dtype=np.int32), kmer)
    return np.asarray(s)[:n]


def control_dtype(name: str):
    return {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}[name]


def compare(got: List, want: List[Mapping], truths: List[int], tol: int
            ) -> dict:
    """The numbers a run is judged by, over every read."""
    pos = sum(g.pos != w.pos for g, w in zip(got, want))
    anchors = sum(g.n_anchors != w.n_anchors for g, w in zip(got, want))
    sw = max((abs(float(g.sw_score) - w.sw_score)
              for g, w in zip(got, want)), default=0.0)
    chain = max((abs(float(g.chain_score) - w.chain_score)
                 / max(abs(w.chain_score), 1.0)
                 for g, w in zip(got, want)), default=0.0)
    misplaced = sum(not (g.pos >= 0 and abs(g.pos - t) <= tol)
                    for g, t in zip(got, truths))
    return {"pos_mismatches": pos, "anchor_mismatches": anchors,
            "sw_score_gap": sw, "chain_score_rel_gap": chain,
            "misplaced_share": misplaced / max(len(got), 1)}


def reference_for(genome, cfg: dict, dtype: Optional[str] = None):
    return Reference(genome, cfg, control_dtype(dtype or "float32"))
