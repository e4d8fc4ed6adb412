"""CSR / sliced-ELLPACK builders (host-side numpy; device consumers in
kernels/ and core/).

The TPU-native relaxation kernel consumes a *by-destination* sliced-ELLPACK
view: for every dst row, a padded list of (in-neighbor id, weight).  Padding
entries point at row 0 with +inf weight so they never win a min.

All builders are fancy-indexed scatters — no per-row Python loops — so the
dynamic engine can afford full rebuilds on ELL capacity overflow (DESIGN.md
§2.3): a rebuild is O(E) numpy work plus one host->device transfer.

Per-window building (DESIGN.md §7.2): ``ell_from_coo`` and
``sliced_ell_from_coo`` take ``row0`` so a caller can build the layout of
one vertex window ``[row0, row0 + n)`` directly from globally-addressed
edges — the sharded engine's per-partition planners build exactly their
owned window this way (dst-owner placement guarantees every edge's dst
falls inside it).  ``row0=0`` is the whole-graph build and the two must
agree block-for-block (test_sliced_layout.py window round-trips).
"""
from __future__ import annotations

import numpy as np

PAD_W = np.float32(np.inf)


def coo_to_csr(n: int, src: np.ndarray, dst: np.ndarray, w: np.ndarray,
               *, by: str = "dst"):
    """Sort COO by row (dst or src); returns (indptr, cols, w_sorted, perm)."""
    rows = dst if by == "dst" else src
    cols = src if by == "dst" else dst
    perm = np.argsort(rows, kind="stable")
    rows_s, cols_s, w_s = rows[perm], cols[perm], w[perm]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, rows_s + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, cols_s, w_s, perm


def _csr_positions(indptr: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(row, column-within-row) for every CSR entry, vectorized."""
    deg = np.diff(indptr)
    rows = np.repeat(np.arange(len(deg)), deg)
    kpos = np.arange(indptr[-1]) - np.repeat(indptr[:-1], deg)
    return rows, kpos


def csr_to_ell(n: int, indptr: np.ndarray, cols: np.ndarray, w: np.ndarray,
               *, k: int | None = None, pad_col: int = 0, n_rows: int | None = None):
    """Dense ELLPACK (n_rows, K) from CSR; K defaults to max row degree.

    Returns (nbr_idx i32[n_rows,K], nbr_w f32[n_rows,K]); pad weight +inf.
    Rows longer than K are truncated (callers pick K >= max degree unless
    deliberately sketching).  ``n_rows >= n`` pads extra all-inf rows at the
    bottom — the engine uses this to round the row count up to the relax
    kernel's block size.
    """
    deg = np.diff(indptr)
    kmax = int(deg.max()) if n and len(cols) else 0
    K = kmax if k is None else k
    K = max(K, 1)
    R = n if n_rows is None else n_rows
    assert R >= n, (R, n)
    idx = np.full((R, K), pad_col, np.int32)
    ww = np.full((R, K), PAD_W, np.float32)
    rows, kpos = _csr_positions(indptr)
    keep = kpos < K
    idx[rows[keep], kpos[keep]] = cols[keep]
    ww[rows[keep], kpos[keep]] = w[keep]
    return idx, ww


def csr_to_sliced_ell(n: int, indptr: np.ndarray, cols: np.ndarray,
                      w: np.ndarray, *, slice_rows: int = 256):
    """Sliced ELLPACK: rows grouped into slices of ``slice_rows``; each slice
    padded to its own max degree.  Returns a list of
    (row_offset, nbr_idx [s,Ks], nbr_w [s,Ks]) — VMEM-friendly blocks with far
    less padding than global ELL on power-law graphs."""
    rows, kpos = _csr_positions(indptr)
    out = []
    for r0 in range(0, n, slice_rows):
        r1 = min(r0 + slice_rows, n)
        deg = np.diff(indptr[r0:r1 + 1])
        Ks = max(1, int(deg.max()) if len(deg) else 1)
        idx = np.zeros((r1 - r0, Ks), np.int32)
        ww = np.full((r1 - r0, Ks), PAD_W, np.float32)
        a, b = indptr[r0], indptr[r1]
        idx[rows[a:b] - r0, kpos[a:b]] = cols[a:b]
        ww[rows[a:b] - r0, kpos[a:b]] = w[a:b]
        out.append((r0, idx, ww))
    return out


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (shared by the layout builders here and
    the engine planners in core/backends/)."""
    m = 1
    while m < x:
        m <<= 1
    return m


def sliced_geometry(widths: list[int], slice_rows: int):
    """Cell addressing of the flat sliced-ELL layout: returns
    ``(offsets i64[S+1], rowk i32[R], base i64[R], total_cells)`` where row
    r's cells occupy ``[base[r], base[r] + rowk[r])``.

    This is THE addressing rule — shared by ``sliced_ell_from_coo`` (rebuild
    placement) and the engine planner (incremental append positions); the
    two must agree bit-for-bit or the device state silently corrupts.
    """
    wid = np.asarray(widths, np.int64)
    offsets = slice_rows * np.r_[0, np.cumsum(wid)]
    rowk = np.repeat(wid, slice_rows).astype(np.int32)
    R = len(widths) * slice_rows
    base = (np.repeat(offsets[:-1], slice_rows)
            + (np.arange(R) % slice_rows) * rowk).astype(np.int64)
    return offsets, rowk, base, int(offsets[-1])


def sliced_ell_from_coo(
    n: int, src: np.ndarray, dst: np.ndarray, w: np.ndarray, *,
    slice_rows: int = 256, hub_k: int = 32, n_rows: int | None = None,
    widths: list[int] | None = None, overflow_capacity: int | None = None,
    row0: int = 0, positions: bool = False,
):
    """Hub-aware hybrid layout: flat sliced-ELL + COO overflow (by dst).

    Rows are grouped into slices of ``slice_rows`` consecutive ids; each
    slice is padded to its own pow2 width ``K_s`` (the slice's max in-degree
    capped at ``hub_k``).  Rows with in-degree > hub_k are *hubs*: their
    first ``hub_k`` in-neighbors (CSR order) stay in the slice, the surplus
    spills into the COO overflow segment.  The ELL cells are flattened into
    one 1-D buffer (slice s at offset ``slice_rows * sum(widths[:s])``, row-
    major within the slice) so incremental patch ops are single scatters at
    planner-computed flat positions regardless of which slice they hit.

    Returns ``(flat_idx i32[L], flat_w f32[L], fill i32[R], widths,
    osrc i32[C], odst i32[C], ow f32[C], n_overflow)`` with
    ``L = slice_rows * sum(widths)``, ``R = n_rows`` (ceil of n to a slice
    multiple), ``C = overflow_capacity`` (pow2, >= surplus edge count).
    Empty/padding cells carry idx 0 / w +inf; padded overflow entries carry
    src=dst=0 / w=+inf — neither can win a min.

    ``widths`` (one pow2 per slice, each >= the slice's capped max degree)
    and ``overflow_capacity`` override the tight defaults — the engine's
    planner passes its monotone-grown values so rebuilds amortize.

    ``row0`` builds the vertex window ``[row0, row0 + n)``: ``dst`` stays
    globally addressed (every value must fall in the window; the returned
    rows and overflow ``odst`` are window-local), ``src`` ids pass through
    untouched — cells always store global in-neighbor ids.

    ``positions=True`` appends ``at i64[E]``: where each input edge landed,
    its flat cell for an ELL edge and ``L + entry`` for an overflow edge —
    what a caller that tombstones by position keeps per edge.
    """
    assert slice_rows >= 1 and slice_rows == next_pow2(slice_rows), slice_rows
    hub_k = next_pow2(max(hub_k, 1))
    dst = np.asarray(dst, np.int64) - row0
    assert not len(dst) or (dst.min() >= 0 and dst.max() < n), \
        f"dst outside window [row0={row0}, row0+{n})"
    indptr, cols, ws, perm = coo_to_csr(n, np.asarray(src), dst,
                                        np.asarray(w), by="dst")
    R = -(-max(n, 1) // slice_rows) * slice_rows if n_rows is None else n_rows
    assert R >= n and R % slice_rows == 0, (R, n, slice_rows)
    n_slices = R // slice_rows
    deg = np.zeros(R, np.int64)
    deg[:n] = np.diff(indptr)
    capped = np.minimum(deg, hub_k)
    slice_max = capped.reshape(n_slices, slice_rows).max(axis=1)
    if widths is None:
        widths = [next_pow2(int(max(k, 1))) for k in slice_max]
    widths = [int(k) for k in widths]
    assert len(widths) == n_slices, (len(widths), n_slices)
    assert all(k == next_pow2(k) and k <= hub_k for k in widths), widths
    assert all(int(m) <= k for m, k in zip(slice_max, widths)), \
        (slice_max.tolist(), widths)

    _, _, base, L = sliced_geometry(widths, slice_rows)
    flat_idx = np.zeros(L, np.int32)
    flat_w = np.full(L, PAD_W, np.float32)
    rows, kpos = _csr_positions(indptr)
    keep = kpos < hub_k
    pos = base[rows[keep]] + kpos[keep]
    flat_idx[pos] = cols[keep]
    flat_w[pos] = ws[keep]

    o_src, o_dst, o_w = cols[~keep], rows[~keep], ws[~keep]
    n_over = len(o_src)
    C = (next_pow2(max(2 * n_over, 8)) if overflow_capacity is None
         else overflow_capacity)
    assert C >= n_over, (C, n_over)
    osrc = np.zeros(C, np.int32)
    odst = np.zeros(C, np.int32)
    ow = np.full(C, PAD_W, np.float32)
    osrc[:n_over] = o_src
    odst[:n_over] = o_dst
    ow[:n_over] = o_w

    fill = capped.astype(np.int32)
    out = (flat_idx, flat_w, fill, widths, osrc, odst, ow, n_over)
    if not positions:
        return out
    at = np.empty(len(perm), np.int64)
    at[perm[keep]] = pos
    at[perm[~keep]] = L + np.arange(n_over)
    return out + (at,)


def ell_from_coo(n: int, src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                 *, k: int, n_rows: int | None = None, row0: int = 0):
    """By-destination ELL directly from COO: (nbr_idx, nbr_w, fill).

    ``fill`` is the per-row occupancy (== in-degree; the incremental
    maintenance path treats it as a high-water mark).  Requires
    ``k >= max in-degree`` — the engine's rebuild policy guarantees it.
    ``row0`` builds the vertex window ``[row0, row0 + n)`` from globally
    addressed ``dst`` (src ids pass through untouched).
    """
    dst = np.asarray(dst, np.int64) - row0
    assert not len(dst) or (dst.min() >= 0 and dst.max() < n), \
        f"dst outside window [row0={row0}, row0+{n})"
    indptr, cols, ws, _ = coo_to_csr(n, np.asarray(src), dst,
                                     np.asarray(w), by="dst")
    deg = np.diff(indptr)
    assert int(deg.max(initial=0)) <= k, (int(deg.max(initial=0)), k)
    idx, ww = csr_to_ell(n, indptr, cols, ws, k=k, n_rows=n_rows)
    R = n if n_rows is None else n_rows
    fill = np.zeros(R, np.int32)
    fill[:n] = deg
    return idx, ww, fill
