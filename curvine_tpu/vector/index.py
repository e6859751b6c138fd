"""IVF ANN index for VectorTable, TPU-native: IVF-flat and IVF-PQ.

Parity surface: the reference's curvine-lancedb re-exports the upstream
Lance `index` module (IVF_PQ etc. — curvine-lancedb/src/lib.rs:25), so
reference users get ANN indexes over cached tables. This is that
capability re-owned TPU-first instead of wrapping a CPU ANN library:

* BUILD — k-means by Lloyd iterations where BOTH steps are MXU work:
  assignment is one [N, D] x [D, C] matmul + argmax, the centroid update
  is a one-hot [C, N] x [N, D] matmul (segment-sum as matmul). Runs
  entirely on device, jitted once per shape. PQ codebooks (Jégou et al.,
  product quantization) train the SAME Lloyd step per subspace.
* LAYOUT — inverted lists as ONE dense [C', L] int32 matrix (global row
  ids, -1 padding). XLA wants static shapes; the round-3 layout padded
  every list to the LONGEST list, so one hot cluster made every probe
  pay its worst case. Now L is clipped at a percentile of the list
  lengths (`cap_pct`) and overflow rows go to SPILL lists: extra matrix
  rows whose centroid entry duplicates their parent's, so they compete
  for probe slots at the parent's score and the search code never
  special-cases them. Probed work becomes ~nprobe·p95 instead of
  nprobe·max. Persisted as an ordinary cached file so it rides the same
  short-circuit/mmap path as row groups.
* SEARCH — chained device stages with NO host round-trip between them.
  IVF-flat: queries x centroids -> top-nprobe lists, take() the
  candidate id matrix, gather candidate vectors from the pinned table,
  batched dot + top_k. IVF-PQ adds the ScaNN-style two-stage scan: an
  ADC pass over 8-bit PQ codes via per-query lookup tables (1 byte per
  subspace of HBM traffic instead of 4·dsub), top-R survivors, then an
  exact fp32/bf16 re-rank whose arithmetic mirrors the brute-force scan
  so returned scores never shift between paths. All static shapes,
  jitted once per shape; the ADC inner loop can run as a fused Pallas
  kernel (tpu/pallas_ops.pq_lut_scan) on TPU.

Freshness follows the Lance model: an index is built at a table
(version, row_groups, deletes) snapshot; table mutations leave it STALE
and knn falls back to the exact brute-force scan until reindexing
(VectorTable.create_index again).
"""

from __future__ import annotations

import json

import numpy as np

from curvine_tpu.common import errors as err

_BUILD_FNS: dict = {}
_SEARCH_FNS: dict = {}
_PQ_SEARCH_FNS: dict = {}
_PQ_ENC_FNS: dict = {}


def _kmeans_step_fn(n: int, d: int, c: int):
    """One Lloyd iteration, jitted per (N, D, C)."""
    key = (n, d, c)
    fn = _BUILD_FNS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def step(vectors, centroids):
            # assignment: nearest centroid by L2 == argmax of the
            # 2*v.c - |c|^2 surrogate — one MXU matmul
            scores = 2.0 * (vectors @ centroids.T) \
                - jnp.sum(centroids * centroids, axis=1)[None, :]
            assign = jnp.argmax(scores, axis=1)
            onehot = jax.nn.one_hot(assign, c, dtype=vectors.dtype)
            sums = onehot.T @ vectors            # [C, D] matmul update
            counts = jnp.sum(onehot, axis=0)[:, None]
            new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0),
                            centroids)           # empty list keeps its seed
            shift = jnp.max(jnp.abs(new - centroids))
            return new, assign, shift

        fn = _BUILD_FNS[key] = jax.jit(step)
    return fn


# ---------------------------------------------------------------- PQ


def _pq_encode_fn(n: int, m: int, dsub: int, ksub: int):
    """Nearest-codeword assignment for all subspaces at once: one
    [N, M, dsub] x [M, ksub, dsub] einsum + argmax, jitted per shape."""
    key = (n, m, dsub, ksub)
    fn = _PQ_ENC_FNS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def enc(v, cbs):
            scores = 2.0 * jnp.einsum("nmd,mkd->nmk", v, cbs,
                                      preferred_element_type=jnp.float32) \
                - jnp.sum(cbs * cbs, axis=2)[None, :, :]
            return jnp.argmax(scores, axis=2).astype(jnp.uint8)

        fn = _PQ_ENC_FNS[key] = jax.jit(enc)
    return fn


class PqCodebook:
    """Product-quantization codebooks: M subspaces of dsub dims, each
    with ksub (<=256) centroids, codes 1 byte per subspace."""

    def __init__(self, codebooks: np.ndarray):
        self.codebooks = np.asarray(codebooks, dtype=np.float32)
        self.m, self.ksub, self.dsub = self.codebooks.shape

    @staticmethod
    def train(vectors: np.ndarray, m: int, ksub: int = 256,
              iters: int = 8, device=None, seed: int = 0,
              sample: int = 65536) -> "PqCodebook":
        """Per-subspace k-means on (a sample of) the vectors; each
        subspace reuses the MXU Lloyd step."""
        import jax

        n, d = vectors.shape
        if d % m:
            raise err.InvalidArgument(f"dim {d} not divisible by pq_m {m}")
        dsub = d // m
        rng = np.random.default_rng(seed)
        if n > sample:
            train_v = vectors[rng.choice(n, size=sample, replace=False)]
        else:
            train_v = vectors
        tn = train_v.shape[0]
        ksub = max(1, min(ksub, 256, tn))
        sub = np.ascontiguousarray(
            train_v.reshape(tn, m, dsub).transpose(1, 0, 2))
        dev = device if device is not None else jax.local_devices()[0]
        step = _kmeans_step_fn(tn, dsub, ksub)
        cbs = []
        for mi in range(m):
            v = jax.device_put(
                np.ascontiguousarray(sub[mi], dtype=np.float32), dev)
            seeds = sub[mi][rng.choice(tn, size=ksub, replace=False)]
            cent = jax.device_put(np.asarray(seeds, dtype=np.float32), dev)
            for _ in range(iters):
                cent, _, shift = step(v, cent)
                if float(shift) < 1e-4:
                    break
            cbs.append(np.asarray(cent))
        return PqCodebook(np.stack(cbs))

    def encode(self, vectors: np.ndarray, device=None,
               chunk: int = 16384, anchors=None) -> np.ndarray:
        """[N, D] -> [N, M] uint8 codes, chunked so the [chunk, M, ksub]
        score tensor never exceeds a few hundred MB on device.

        anchors=(centers [C, D], assign [N]) encodes RESIDUALS
        vectors[i] - centers[assign[i]] (the Jégou IVF-ADC form —
        codewords only need to cover the residual scale, not the whole
        space) without ever materializing the [N, D] residual array."""
        import jax

        n, d = vectors.shape
        if d != self.m * self.dsub:
            raise err.InvalidArgument(
                f"encode dim {d} != {self.m}x{self.dsub}")
        dev = device if device is not None else jax.local_devices()[0]
        cbs = jax.device_put(self.codebooks, dev)
        out = np.empty((n, self.m), dtype=np.uint8)
        chunk = min(chunk, max(1, n))
        fn = _pq_encode_fn(chunk, self.m, self.dsub, self.ksub)
        for off in range(0, n, chunk):
            part = np.asarray(vectors[off:off + chunk], dtype=np.float32)
            if anchors is not None:
                centers, assign = anchors
                part = part - centers[assign[off:off + chunk]]
            if part.shape[0] < chunk:      # pad the tail to the one shape
                part = np.concatenate([part, np.zeros(
                    (chunk - part.shape[0], d), dtype=np.float32)])
            codes = np.asarray(fn(jax.device_put(
                part.reshape(chunk, self.m, self.dsub), dev), cbs))
            out[off:off + chunk] = codes[:min(chunk, n - off)]
        return out

    def decode(self, codes: np.ndarray) -> np.ndarray:
        """[N, M] uint8 -> reconstructed [N, D] f32 (codeword lookup)."""
        codes = np.asarray(codes)
        parts = [self.codebooks[mi][codes[:, mi].astype(np.int64)]
                 for mi in range(self.m)]
        return np.concatenate(parts, axis=1)


# ---------------------------------------------------------------- search


def _search_fn(metric: str, k: int, nprobe: int, qchunk: int = 16):
    key = (metric, k, nprobe, qchunk)
    fn = _SEARCH_FNS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def one_chunk(q, centroids, lists, v_pad, ids_pad):
            """q [Qc,D]; centroids [C',D]; lists [C',L] dense-row ids into
            v_pad (-1 pad); v_pad/ids_pad are the table's ONE pinned
            sentinel-padded array pair ([N+1,D] with a zero row at index
            N / [N+1] with -1) — shared with the exact scan, no second
            device copy."""
            qn = jnp.linalg.norm(q, axis=1, keepdims=True).clip(1e-12)
            if metric == "cosine":
                cn = centroids / jnp.linalg.norm(
                    centroids, axis=1, keepdims=True).clip(1e-12)
                cs = (q / qn) @ cn.T
            else:
                cs = 2.0 * (q @ centroids.T) \
                    - jnp.sum(centroids * centroids, axis=1)[None, :]
            _, probe = jax.lax.top_k(cs, nprobe)        # [Qc, nprobe]
            cand = jnp.take(lists, probe, axis=0)       # [Qc, nprobe, L]
            cand = cand.reshape(q.shape[0], -1)         # [Qc, nprobe*L]
            sentinel = v_pad.shape[0] - 1
            slot = jnp.where(cand < 0, sentinel, cand)
            cv = jnp.take(v_pad, slot, axis=0)          # [Qc, M, D]
            # mirror the exact scan's arithmetic EXACTLY (same casts:
            # bf16 q × bf16 table, f32 accumulation; norms in f32) —
            # scores must not shift when the index goes stale and knn
            # falls back to the exact scan
            dots = jnp.einsum("qd,qmd->qm", q.astype(cv.dtype), cv,
                              preferred_element_type=jnp.float32)
            if metric == "cosine":
                scores = dots / qn
            else:
                cvf = cv.astype(jnp.float32)
                scores = -(jnp.sum(q * q, axis=1)[:, None]
                           - 2.0 * dots + jnp.sum(cvf * cvf, axis=2))
            scores = jnp.where(cand < 0, -jnp.inf, scores)
            kk = min(k, int(scores.shape[1]))
            s, idx = jax.lax.top_k(scores, kk)
            rows = jnp.take_along_axis(slot, idx, axis=1)
            return s, jnp.take(ids_pad, rows)

        def search(q, centroids, lists, v_pad, ids_pad):
            """Batched entry: large query batches are processed in
            `qchunk`-query slices via lax.map INSIDE the one compiled
            program (one dispatch per batch) — the [Qc, nprobe·L, D]
            candidate gather is the peak-memory term, so serving batches
            of 256+ queries must not materialize it for the whole batch
            at once (500K rows × nprobe 8 would be gigabytes)."""
            Q = q.shape[0]
            if Q <= qchunk:
                return one_chunk(q, centroids, lists, v_pad, ids_pad)
            pad = (-Q) % qchunk
            qp = jnp.pad(q, ((0, pad), (0, 0))) if pad else q
            qs = qp.reshape(-1, qchunk, q.shape[1])
            s, i = jax.lax.map(
                lambda qq: one_chunk(qq, centroids, lists, v_pad, ids_pad),
                qs)
            return (s.reshape(-1, s.shape[-1])[:Q],
                    i.reshape(-1, i.shape[-1])[:Q])

        fn = _SEARCH_FNS[key] = jax.jit(search)
    return fn


def _pq_search_fn(metric: str, k: int, nprobe: int, rerank: int,
                  use_pallas: bool, interpret: bool, qchunk: int = 16):
    """Two-stage IVF-PQ search, jitted per shape-determining config:
    (1) queries × centroids → top-nprobe lists; (2) residual-ADC scan —
    x ≈ c_list + r̂(code), so the score splits into a per-list constant
    (one [Qc, C'] matmul, shared with probing) plus a per-query LUT
    [M, ksub] over RESIDUAL codewords, and every candidate is scored by
    summing M one-byte table lookups (codes arrive pre-offset int32 so
    the scan is one gather + one reduce, no index arithmetic passes);
    (3) top-`rerank` ADC survivors are re-scored EXACTLY against the
    pinned fp32/bf16 table with the same arithmetic as the brute-force
    scan, then top-k. No host round-trip between stages."""
    key = (metric, k, nprobe, rerank, use_pallas, interpret, qchunk)
    fn = _PQ_SEARCH_FNS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def one_chunk(q, centroids, lists, cbs, codes_pad, norms_pad,
                      v_pad, ids_pad):
            m, ksub, dsub = cbs.shape
            L = lists.shape[1]
            qn = jnp.linalg.norm(q, axis=1, keepdims=True).clip(1e-12)
            cdot = q @ centroids.T                        # [Qc, C']
            if metric == "cosine":
                cnorm = jnp.linalg.norm(centroids, axis=1).clip(1e-12)
                cs = (cdot / qn) / cnorm[None, :]
            else:
                cnorm2 = jnp.sum(centroids * centroids, axis=1)
                cs = 2.0 * cdot - cnorm2[None, :]
            _, probe = jax.lax.top_k(cs, nprobe)
            cand = jnp.take(lists, probe, axis=0).reshape(q.shape[0], -1)
            sentinel = v_pad.shape[0] - 1
            slot = jnp.where(cand < 0, sentinel, cand)    # [Qc, W]

            # --- stage 2: residual ADC (M bytes of code traffic per
            # candidate instead of 4·D for fp32 rows). x ≈ c + r̂:
            #   cosine: q·x ≈ q·c (per-list const) + Σ_m q_m·r̂_m (LUT)
            #   l2 (2q·x - |x|² surrogate): 2q·c + Σ_m 2q_m·r̂_m
            #        - |x̂|² (per-row norms, built with the codes)
            qs = q.reshape(q.shape[0], m, dsub)
            lut = jnp.einsum("qmd,mkd->qmk", qs, cbs,
                             preferred_element_type=jnp.float32)
            cprobe = jnp.take_along_axis(cdot, probe, axis=1)
            if metric == "l2":
                lut = 2.0 * lut
                cprobe = 2.0 * cprobe
            const = jnp.repeat(cprobe, L, axis=1)         # [Qc, W]
            codes = jnp.take(codes_pad, slot, axis=0)     # [Qc, W, M] i32
            if use_pallas:
                from curvine_tpu.tpu.pallas_ops import pq_lut_scan
                adc = jax.vmap(
                    lambda lt, cd: pq_lut_scan(
                        lt, cd, interpret=interpret,
                        pre_offset=True))(lut, codes)     # [Qc, W]
            else:
                adc = jnp.sum(jnp.take_along_axis(
                    lut.reshape(q.shape[0], 1, m * ksub),
                    codes, axis=2), axis=2)               # [Qc, W]
            adc = adc + const
            if metric == "l2":
                adc = adc - jnp.take(norms_pad, slot)
            adc = jnp.where(cand < 0, -jnp.inf, adc)

            # --- stage 3: exact re-rank of the top-R ADC survivors,
            # arithmetic identical to the brute-force scan so scores do
            # not shift between the PQ, flat, and exact paths
            rr = min(rerank, int(adc.shape[1]))
            _, r_idx = jax.lax.top_k(adc, rr)             # [Qc, R]
            r_slot = jnp.take_along_axis(slot, r_idx, axis=1)
            r_cand = jnp.take_along_axis(cand, r_idx, axis=1)
            cv = jnp.take(v_pad, r_slot, axis=0)          # [Qc, R, D]
            dots = jnp.einsum("qd,qrd->qr", q.astype(cv.dtype), cv,
                              preferred_element_type=jnp.float32)
            if metric == "cosine":
                scores = dots / qn
            else:
                cvf = cv.astype(jnp.float32)
                scores = -(jnp.sum(q * q, axis=1)[:, None]
                           - 2.0 * dots + jnp.sum(cvf * cvf, axis=2))
            scores = jnp.where(r_cand < 0, -jnp.inf, scores)
            kk = min(k, rr)
            s, idx = jax.lax.top_k(scores, kk)
            rows = jnp.take_along_axis(r_slot, idx, axis=1)
            return s, jnp.take(ids_pad, rows)

        def search(q, centroids, lists, cbs, codes_pad, norms_pad,
                   v_pad, ids_pad):
            Q = q.shape[0]
            if Q <= qchunk:
                return one_chunk(q, centroids, lists, cbs, codes_pad,
                                 norms_pad, v_pad, ids_pad)
            pad = (-Q) % qchunk
            qp = jnp.pad(q, ((0, pad), (0, 0))) if pad else q
            qs = qp.reshape(-1, qchunk, q.shape[1])
            s, i = jax.lax.map(
                lambda qq: one_chunk(qq, centroids, lists, cbs,
                                     codes_pad, norms_pad, v_pad,
                                     ids_pad), qs)
            return (s.reshape(-1, s.shape[-1])[:Q],
                    i.reshape(-1, i.shape[-1])[:Q])

        fn = _PQ_SEARCH_FNS[key] = jax.jit(search)
    return fn


def _capped_layout(assign: np.ndarray, nlist: int, cap_pct: float
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Pack cluster members into a dense [C+S, cap] id matrix. cap is
    the cap_pct-percentile list length; clusters longer than cap get
    SPILL rows appended after the primaries, and `owner[row]` names the
    centroid each matrix row belongs to (owner[c]=c for primaries).
    Falls back to the plain max-length layout when capping would not
    shrink the matrix by >=10% (tiny/uniform tables)."""
    counts = np.bincount(assign, minlength=nlist)
    max_len = max(int(counts.max()) if counts.size else 1, 1)
    cap = max_len
    if cap_pct < 100.0 and counts.size:
        pcap = max(1, int(np.ceil(np.percentile(counts, cap_pct))))
        if pcap < max_len:
            spills = int(np.sum(np.maximum(
                np.ceil(counts / pcap).astype(np.int64) - 1, 0)))
            if (nlist + spills) * pcap < 0.9 * nlist * max_len:
                cap = pcap
    order = np.argsort(assign, kind="stable").astype(np.int32)
    bounds = np.concatenate([[0], np.cumsum(counts)])
    extra = np.maximum(np.ceil(counts / cap).astype(np.int64) - 1, 0)
    total = nlist + int(extra.sum())
    lists = np.full((total, cap), -1, dtype=np.int32)
    owner = np.arange(total, dtype=np.int32)
    spill = nlist
    for c in range(nlist):
        members = order[bounds[c]:bounds[c + 1]]
        lists[c, :min(cap, members.size)] = members[:cap]
        for off in range(cap, members.size, cap):
            part = members[off:off + cap]
            lists[spill, :part.size] = part
            owner[spill] = c
            spill += 1
    return lists, owner


class IvfIndex:
    """Device-side state + persistence for one table's IVF index
    (flat or PQ)."""

    def __init__(self, nlist: int, centroids: np.ndarray,
                 lists: np.ndarray, built_at: dict,
                 pq: PqCodebook | None = None,
                 codes: np.ndarray | None = None,
                 norms: np.ndarray | None = None):
        self.nlist = nlist                # logical k-means lists
        self.centroids = centroids        # [C+S, D] f32 (spill rows
        #                                   duplicate their parent's)
        self.lists = lists                # [C+S, L] i32 dense-row ids,
        #                                   -1 pad
        self.built_at = built_at          # table snapshot id
        self.pq = pq                      # PqCodebook | None
        self.codes = codes                # [N, M] uint8 RESIDUAL codes,
        #                                   dense-row order
        self.norms = norms                # [N] f32 |ĉ+r̂|² (l2 ADC term)
        self._dev: dict = {}

    @property
    def nlist_total(self) -> int:
        """Physical list count including spill lists."""
        return int(self.lists.shape[0])

    # ---------------- build ----------------

    @staticmethod
    def build(vectors: np.ndarray, dense_ids: np.ndarray, nlist: int,
              built_at: dict, iters: int = 10, device=None,
              seed: int = 0, cap_pct: float = 95.0,
              pq_m: int | None = None, pq_ksub: int = 256,
              pq_iters: int = 8, pq_sample: int = 65536) -> "IvfIndex":
        """K-means on device over the LIVE vectors ([N, D] host array,
        dense row index i ↔ dense_ids[i] position in the pinned table).
        pq_m != None additionally trains PQ codebooks (pq_m subspaces,
        pq_ksub codewords each) and packs one uint8 code row per
        vector."""
        import jax

        n, d = vectors.shape
        nlist = max(1, min(nlist, n))
        rng = np.random.default_rng(seed)
        seeds = vectors[rng.choice(n, size=nlist, replace=False)]
        dev = device if device is not None else jax.local_devices()[0]
        v = jax.device_put(np.asarray(vectors, dtype=np.float32), dev)
        cent = jax.device_put(np.asarray(seeds, dtype=np.float32), dev)
        step = _kmeans_step_fn(n, d, nlist)
        assign = None
        for _ in range(iters):
            cent, assign, shift = step(v, cent)
            if float(shift) < 1e-4:
                break
        assign = np.asarray(assign)
        centroids = np.asarray(cent)
        # dense [C+S, cap] id matrix: rows ARE dense indices into the
        # pinned table (the search takes vectors by these); spill rows
        # share their parent's centroid so top-nprobe naturally probes
        # them without any chain-following
        lists, owner = _capped_layout(assign, nlist, cap_pct)
        pq = None
        codes = None
        norms = None
        if pq_m:
            # PQ on RESIDUALS x - c_assigned (Jégou IVF-ADC): codewords
            # cover the residual scale, not the whole space, so within-
            # list ranking survives quantization. Train on a sample;
            # encode chunked (no [N, D] residual array is materialized).
            sidx = rng.choice(n, size=min(n, pq_sample), replace=False)
            resid_sample = vectors[sidx] - centroids[assign[sidx]]
            pq = PqCodebook.train(resid_sample, pq_m, ksub=pq_ksub,
                                  iters=pq_iters, device=dev, seed=seed,
                                  sample=pq_sample)
            codes = pq.encode(vectors, device=dev,
                              anchors=(centroids, assign))
            # per-row |x̂|² for the l2 ADC term, chunked like encode
            norms = np.empty(n, dtype=np.float32)
            for off in range(0, n, 16384):
                part = codes[off:off + 16384]
                recon = pq.decode(part) \
                    + centroids[assign[off:off + 16384]]
                norms[off:off + 16384] = np.sum(recon * recon, axis=1)
        centroids = centroids[owner]
        return IvfIndex(nlist, centroids, lists, built_at, pq=pq,
                        codes=codes, norms=norms)

    # ---------------- persistence ----------------

    def to_bytes(self) -> bytes:
        meta = {
            "fmt": 2, "nlist": self.nlist,
            "nlist_total": int(self.lists.shape[0]),
            "dim": int(self.centroids.shape[1]),
            "list_cap": int(self.lists.shape[1]),
            "built_at": self.built_at, "pq": None}
        if self.pq is not None:
            meta["pq"] = {"m": self.pq.m, "ksub": self.pq.ksub,
                          "dsub": self.pq.dsub,
                          "rows": int(self.codes.shape[0])}
        mb = json.dumps(meta).encode()
        parts = [np.int64(len(mb)).tobytes(), mb,
                 self.centroids.astype(np.float32).tobytes(),
                 self.lists.astype(np.int32).tobytes()]
        if self.pq is not None:
            parts.append(self.pq.codebooks.astype(np.float32).tobytes())
            parts.append(self.codes.astype(np.uint8).tobytes())
            parts.append(self.norms.astype(np.float32).tobytes())
        return b"".join(parts)

    @staticmethod
    def from_bytes(buf) -> "IvfIndex":
        view = np.frombuffer(buf, dtype=np.uint8)
        mlen = int(view[:8].view(np.int64)[0])
        meta = json.loads(view[8:8 + mlen].tobytes())
        off = 8 + mlen
        d, cap = meta["dim"], meta["list_cap"]
        # fmt 1 (pre-PQ) files have no nlist_total/pq keys
        ct = meta.get("nlist_total", meta["nlist"])
        cent = view[off:off + ct * d * 4].view(np.float32).reshape(ct, d)
        off += ct * d * 4
        lists = view[off:off + ct * cap * 4].view(np.int32).reshape(
            ct, cap)
        off += ct * cap * 4
        pq = None
        codes = None
        norms = None
        pmeta = meta.get("pq")
        if pmeta:
            m, ksub, dsub = pmeta["m"], pmeta["ksub"], pmeta["dsub"]
            cbs = view[off:off + m * ksub * dsub * 4].view(
                np.float32).reshape(m, ksub, dsub)
            off += m * ksub * dsub * 4
            rows = pmeta["rows"]
            codes = view[off:off + rows * m].reshape(rows, m)
            off += rows * m
            norms = view[off:off + rows * 4].view(np.float32)
            pq = PqCodebook(np.array(cbs))
        return IvfIndex(meta["nlist"], cent, lists, meta["built_at"],
                        pq=pq, codes=codes, norms=norms)

    # ---------------- search ----------------

    def _device_state(self, device):
        import jax

        dev_key = getattr(device, "id", device)
        got = self._dev.get(dev_key)
        if got is None:
            got = {"cent": jax.device_put(self.centroids, device),
                   "lists": jax.device_put(self.lists, device)}
            if self.pq is not None:
                # sentinel-padded codes pinned as PRE-OFFSET int32:
                # codes[i, m] + m·ksub indexes the flattened [M·ksub]
                # LUT directly, so the per-query ADC is one gather + one
                # reduce with no widening/offset passes over the [W, M]
                # tensor. Row N is the sentinel the -1 list padding maps
                # to (masked out of the ADC scores, same convention as
                # the pinned vector sentinel row).
                offs = (np.arange(self.pq.m, dtype=np.int32)
                        * self.pq.ksub)[None, :]
                codes_pad = np.concatenate(
                    [self.codes.astype(np.int32) + offs,
                     np.broadcast_to(offs, (1, self.pq.m))])
                norms_pad = np.concatenate(
                    [np.asarray(self.norms, dtype=np.float32),
                     np.zeros(1, dtype=np.float32)])
                got["cbs"] = jax.device_put(self.pq.codebooks, device)
                got["codes"] = jax.device_put(codes_pad, device)
                got["norms"] = jax.device_put(norms_pad, device)
            self._dev = {dev_key: got}
        return got

    def search(self, query: np.ndarray, v_pinned, ids_pinned, k: int,
               metric: str, nprobe: int, device,
               use_pq: bool | str = "auto", rerank: int | None = None,
               pallas: bool | str = "auto"):
        """v_pinned/ids_pinned: the table's ONE pinned sentinel-padded
        device array pair (LIVE rows + zero/-1 sentinel, normalized per
        metric) — shared with the exact scan; only centroids + lists
        (+ PQ codes) add device residency here.

        use_pq: "auto" uses the ADC path iff PQ codes were built;
        rerank: ADC survivors re-scored exactly (default max(4k, 32));
        pallas: "auto" fuses the ADC scan as a Pallas kernel on TPU;
        True forces it (compiled — interpret mode only where `device`
        is a CPU device, i.e. the tests)."""
        import jax

        if use_pq == "auto":
            use_pq = self.pq is not None
        elif use_pq and self.pq is None:
            raise err.InvalidArgument(
                "index has no PQ codes (create_index(pq_m=...))")
        nprobe = max(1, min(nprobe, self.nlist_total))
        state = self._device_state(device)
        q = jax.device_put(
            np.atleast_2d(np.asarray(query, dtype=np.float32)), device)
        if not use_pq:
            return _search_fn(metric, k, nprobe)(
                q, state["cent"], state["lists"], v_pinned, ids_pinned)
        width = nprobe * int(self.lists.shape[1])
        rr = max(k, min(rerank if rerank else max(4 * k, 32), width))
        platform = getattr(device, "platform", "")
        use_pallas = pallas is True or (pallas == "auto"
                                        and platform == "tpu")
        interpret = platform == "cpu"
        fn = _pq_search_fn(metric, k, nprobe, rr, use_pallas, interpret)
        return fn(q, state["cent"], state["lists"], state["cbs"],
                  state["codes"], state["norms"], v_pinned, ids_pinned)


def table_snapshot(table) -> dict:
    """The freshness id an index is built against."""
    return {"version": table.version, "row_groups": table.row_groups,
            "deletes": len(table._deletes or ())}
