"""Optional native (C, via ctypes) kernels for the CPU hot paths.

Three kernel families share one lazily-compiled ``.so``:

**Routing** (``route_forest``): the batched numpy router pays ~10 numpy
passes per tree level; XLA pays full ``max_depth`` for every lane because it
cannot compact dynamically.  A tiny C loop does what neither can: per-lane
early exit with one fused pass, at a few ns per (sample, tree) step.  A
batch is split into (tree × row block) tasks over every OpenMP thread, the
grain chosen from its shape (:func:`route_grain`).

**Proximity** (``prox_bucket`` / ``prox_gather`` / ``prox_block``): the
factored SWLC product P V = Q (Wᵀ V) as two fused passes over the dense
``(gl, q, w)`` factor arrays — bucket reference rows into the (L, C) leaf
table, then gather per query row — plus the dense collision block
P[i, j] = Σ_t q[i,t] w[j,t] 1[gl_q[i,t] = gl_w[j,t]].  These are the
``ProximityEngine(backend="native")`` primitives for out-of-sample serving:
the bucket table depends only on the reference side, so the engine caches it
across serving ticks and each tick pays O(n_query · T · C) gather only.

**Training** (``train_level`` / ``train_hist`` / ``train_best_split`` /
``train_partition``): the level-wise histogram trainer's three hot loops.
``train_level`` fuses per-node histogram accumulation and best-split
scoring over one cache-resident scratch buffer per thread (OpenMP over
nodes), so levels with thousands of small nodes never materialize a giant
mostly-empty histogram; the two-phase ``train_hist`` (feature-striped, for
intra-node parallelism on narrow levels) + ``train_best_split`` pair and
``train_partition`` complete the family.  All accumulate in float64 in the
numpy trainer's exact operation order (see ``forest/training.py``), so
``tree_backend="native"`` grows bit-identical trees to the numpy path.

The kernels are compiled **lazily** with whatever ``cc``/``gcc`` the host
has, cached under ``_native_build/`` next to this module (keyed by source
hash), and loaded through ctypes — no build-time dependency, no pip install.
If no compiler is available the caller falls back to the numpy/scipy paths;
everything is gated behind :func:`available`.

Exactness: the routing predicate is identical to the numpy/oracle path
(``x > float64(threshold)`` sends a sample right), so results are
bit-identical to ``route_tree``; the proximity kernels accumulate in float64
like the scipy reference.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from pathlib import Path
from typing import Optional

import numpy as np

__all__ = ["available", "route_grain", "route_native",
           "prox_bucket_native", "prox_gather_native", "prox_matmat_native",
           "prox_block_native",
           "train_hist_native", "train_best_split_native",
           "train_level_native", "train_partition_native"]

_SOURCE = r"""
#include <stdint.h>
#include <string.h>
#include <math.h>
#ifdef _OPENMP
#include <omp.h>
#endif

/* Route a sample batch through every tree.  Layouts:
 *   X:    (n, d) float64, C-order
 *   feature/leaf: (T*M,) int32  -- node t*M+j is tree t's node j
 *   thr:  (T*M,) float64
 *   lr:   (2*T*M,) int32 global child ids, [2g]=left [2g+1]=right
 *   out:  (n, T) int32, C-order
 * One task walks one block of row_block rows through one tree.  Tasks are
 * numbered tree-major, so a thread's next task is usually the same tree on
 * the next block, its node table still in cache; trees differ in depth,
 * hence dynamic scheduling.  n_threads == 1 opens no parallel region.
 */
void route_forest(const double *X, int64_t n, int64_t d,
                  const int32_t *feature, const double *thr,
                  const int32_t *lr, const int32_t *leaf,
                  int64_t T, int64_t M, int64_t row_block, int64_t n_threads,
                  int32_t *out)
{
    const int64_t n_blocks = (n + row_block - 1) / row_block;
    #pragma omp parallel for schedule(dynamic, 1) num_threads(n_threads) \
        if (n_threads > 1)
    for (int64_t k = 0; k < n_blocks * T; ++k) {
        const int64_t t = k / n_blocks, i0 = (k % n_blocks) * row_block;
        const int64_t i1 = i0 + row_block < n ? i0 + row_block : n;
        const int32_t root = (int32_t)(t * M);
        for (int64_t i = i0; i < i1; ++i) {
            const double *x = X + i * d;
            int32_t node = root;
            int32_t f = feature[node];
            while (f >= 0) {
                /* !(x <= thr) so NaN goes right, matching the oracle */
                node = lr[2 * node + !(x[f] <= thr[node])];
                f = feature[node];
            }
            out[i * T + t] = leaf[node];
        }
    }
}

/* ---- SWLC proximity kernels (ProximityEngine backend="native") ----
 *
 * Factor layouts (row-major, all contiguous):
 *   gl: (n, T) int64 global leaf ids     q/w: (n, T) float64 SWLC weights
 *   V:  (n, C) float64                   s:   (L, C) float64 bucket table
 */

/* Bucket stage of P V = Q (Wᵀ V): s[gl_w[j,t], c] += w[j,t] · V[j,c].
 * The leaf scatter races under a naive omp-for, so parallelism is over
 * column stripes: every thread walks all rows but owns a disjoint slice of
 * C — no atomics, no per-thread (L, C) copies. */
void prox_bucket(const int64_t *gl_w, const double *w, int64_t nw, int64_t T,
                 const double *V, int64_t C, double *s)
{
    #pragma omp parallel
    {
        int64_t nth = 1, tid = 0;
        #ifdef _OPENMP
        nth = omp_get_num_threads(); tid = omp_get_thread_num();
        #endif
        int64_t c0 = tid * C / nth, c1 = (tid + 1) * C / nth;
        if (c1 > c0) {
            for (int64_t j = 0; j < nw; ++j) {
                const double *vj = V + j * C;
                for (int64_t t = 0; t < T; ++t) {
                    double wj = w[j * T + t];
                    if (wj == 0.0) continue;
                    double *sl = s + gl_w[j * T + t] * C;
                    for (int64_t c = c0; c < c1; ++c) sl[c] += wj * vj[c];
                }
            }
        }
    }
}

/* Gather stage: out[i,c] = Σ_t q[i,t] · s[gl_q[i,t], c]. */
void prox_gather(const int64_t *gl_q, const double *q, int64_t nq, int64_t T,
                 const double *s, int64_t C, double *out)
{
    #pragma omp parallel for schedule(static)
    for (int64_t i = 0; i < nq; ++i) {
        const int64_t *g = gl_q + i * T;
        const double *qi = q + i * T;
        double *o = out + i * C;
        for (int64_t c = 0; c < C; ++c) o[c] = 0.0;
        for (int64_t t = 0; t < T; ++t) {
            double qt = qi[t];
            if (qt == 0.0) continue;
            const double *sl = s + g[t] * C;
            for (int64_t c = 0; c < C; ++c) o[c] += qt * sl[c];
        }
    }
}

/* ---- level-wise histogram training kernels (tree_backend="native") ----
 *
 * Layouts: Xb (n, d) uint8 bin codes, C-order; per-level instance arrays
 * (rows/w/y) sorted by node with node ranges in bounds (gc+1); hist is
 * (gc, d, B, C) float64.  One of ycls/yreg is NULL depending on the task.
 *
 * Conformance contract with the numpy trainer: every (node, feature-stripe)
 * histogram column is owned by ONE thread which walks that node's samples
 * in order, so each bin's float64 accumulation order is identical to
 * numpy's bincount; the scoring loops mirror numpy's operation order
 * exactly (sequential per-channel bin cumsum, sequential channel sums,
 * first-maximum tie-breaks), so trees come out bit-identical.
 */
void train_hist(const uint8_t *Xb, int64_t d,
                const int64_t *rows, const double *w,
                const int64_t *ycls, const double *yreg,
                const int64_t *bounds, int64_t gc,
                int64_t B, int64_t C, int is_cls, int64_t n_stripes,
                double *hist)
{
    #pragma omp parallel for collapse(2) schedule(dynamic, 1)
    for (int64_t g = 0; g < gc; ++g) {
        for (int64_t s = 0; s < n_stripes; ++s) {
            int64_t f0 = s * d / n_stripes, f1 = (s + 1) * d / n_stripes;
            if (f1 <= f0) continue;
            double *hg = hist + g * d * B * C;
            for (int64_t i = bounds[g]; i < bounds[g + 1]; ++i) {
                const uint8_t *xr = Xb + rows[i] * d;
                if (is_cls) {
                    double wi = w[i];
                    int64_t c = ycls[i];
                    for (int64_t f = f0; f < f1; ++f)
                        hg[(f * B + xr[f]) * C + c] += wi;
                } else {
                    double wi = w[i], yi = yreg[i];
                    double wy = wi * yi, wy2 = wi * (yi * yi);
                    for (int64_t f = f0; f < f1; ++f) {
                        double *hb = hg + (f * B + xr[f]) * 3;
                        hb[0] += wi; hb[1] += wy; hb[2] += wy2;
                    }
                }
            }
        }
    }
}

/* Score one node's (d, B, C) histogram: best (feature, bin) split.
 * u_g: (d, B) uniform draws for splitter="random" (NULL for "best");
 * mask_g: (d,) feature-subset mask (NULL when all features).  Mirrors the
 * numpy ``_best_splits`` operation order exactly: sequential per-channel
 * bin cumsum, sequential channel sums, first-maximum tie-breaks.  Once the
 * right side is exactly empty (nR == 0) the remaining bins are either
 * invalid or identical in gain to the current one, so they can be skipped
 * without changing any result (for "random" this needs msl > 0, which
 * makes empty-right bins invalid). */
static void score_node(const double *hg, int64_t d, int64_t B, int64_t C,
                       int is_cls, double msl,
                       const double *u_g, const uint8_t *mask_g,
                       double *bg_out, int64_t *bf_out, int64_t *bb_out,
                       double *tot)
{
    for (int64_t c = 0; c < C; ++c) tot[c] = 0.0;
    for (int64_t b = 0; b < B; ++b)
        for (int64_t c = 0; c < C; ++c) tot[c] += hg[b * C + c];
    double parent = 0.0;
    if (is_cls) {
        double sq = 0.0, sm = 0.0;
        for (int64_t c = 0; c < C; ++c) {
            double v = tot[c];
            sq += v * v; sm += v;
        }
        parent = sq / (sm > 1e-12 ? sm : 1e-12);
    }
    int can_skip = (u_g == 0) || (msl > 0.0);
    double best_g = -INFINITY; int64_t best_f = 0, best_b = 0;
    double tf[C]; double cum[C];        /* VLAs: C = n channels */
    for (int64_t f = 0; f < d; ++f) {
        const double *hf = hg + f * B * C;
        for (int64_t c = 0; c < C; ++c) tf[c] = 0.0;
        for (int64_t b = 0; b < B; ++b)
            for (int64_t c = 0; c < C; ++c) tf[c] += hf[b * C + c];
        double par_f = parent;
        if (!is_cls)
            par_f = tf[1] * tf[1] / (tf[0] > 1e-12 ? tf[0] : 1e-12);
        for (int64_t c = 0; c < C; ++c) cum[c] = 0.0;
        double fg = -INFINITY, fu = -INFINITY;
        int64_t fb = 0;
        for (int64_t b = 0; b + 1 < B; ++b) {       /* last bin invalid */
            for (int64_t c = 0; c < C; ++c) cum[c] += hf[b * C + c];
            double nL, nR, sc;
            if (is_cls) {
                double SL = 0.0, SR = 0.0;
                nL = 0.0; nR = 0.0;
                for (int64_t c = 0; c < C; ++c) {
                    double l = cum[c], r = tf[c] - l;
                    nL += l; nR += r; SL += l * l; SR += r * r;
                }
                sc = SL / (nL > 1e-12 ? nL : 1e-12)
                   + SR / (nR > 1e-12 ? nR : 1e-12);
            } else {
                nL = cum[0]; nR = tf[0] - cum[0];
                double l1 = cum[1], r1 = tf[1] - cum[1];
                sc = l1 * l1 / (nL > 1e-12 ? nL : 1e-12)
                   + r1 * r1 / (nR > 1e-12 ? nR : 1e-12);
            }
            if (nL >= msl && nR >= msl) {
                if (u_g) {              /* random bin among valid ones */
                    double uv = u_g[f * B + b];
                    if (uv > fu) { fu = uv; fb = b; fg = sc - par_f; }
                } else {
                    double gn = sc - par_f;
                    if (gn > fg) { fg = gn; fb = b; }
                }
            }
            if (nR == 0.0 && can_skip) break;
        }
        if (mask_g && !mask_g[f]) fg = -INFINITY;
        if (f == 0 || fg > best_g) {
            best_g = fg; best_f = f; best_b = fb;
        }
    }
    *bg_out = best_g; *bf_out = best_f; *bb_out = best_b;
}

/* Best (feature, bin) split per node from (gc, d, B, C) histograms.
 * Outputs: gain/feature/bin per node + node totals (feature-0 column,
 * the numpy path's convention). */
void train_best_split(const double *hist, int64_t gc, int64_t d, int64_t B,
                      int64_t C, int is_cls, double msl,
                      const double *u, const uint8_t *mask,
                      double *bg_out, int64_t *bf_out, int64_t *bb_out,
                      double *tot_out)
{
    #pragma omp parallel for schedule(dynamic, 4)
    for (int64_t g = 0; g < gc; ++g)
        score_node(hist + g * d * B * C, d, B, C, is_cls, msl,
                   u ? u + g * d * B : 0, mask ? mask + g * d : 0,
                   bg_out + g, bf_out + g, bb_out + g, tot_out + g * C);
}

/* Worker-count probe so the caller can allocate per-thread scratch. */
int64_t max_threads(void)
{
    #ifdef _OPENMP
    return (int64_t)omp_get_max_threads();
    #else
    return 1;
    #endif
}

/* Fused per-node histogram + best-split.  Each thread owns whole nodes and
 * re-uses one scratch histogram (d*B*C doubles, a row of the
 * caller-allocated (max_threads, d*B*C) buffer) that stays cache-resident,
 * so levels with thousands of small nodes never allocate, zero, or scan a
 * giant mostly-empty (gc, d, B, C) buffer.  Accumulation order per bin and
 * scoring arithmetic are identical to train_hist + train_best_split. */
void train_level(const uint8_t *Xb, int64_t d,
                 const int64_t *rows, const double *w,
                 const int64_t *ycls, const double *yreg,
                 const int64_t *bounds, int64_t gc,
                 int64_t B, int64_t C, int is_cls, double msl,
                 const double *u, const uint8_t *mask, double *scratch,
                 double *bg_out, int64_t *bf_out, int64_t *bb_out,
                 double *tot_out)
{
    #pragma omp parallel
    {
        int64_t tid = 0;
        #ifdef _OPENMP
        tid = omp_get_thread_num();
        #endif
        double *hg = scratch + tid * d * B * C;
        #pragma omp for schedule(dynamic, 2)
        for (int64_t g = 0; g < gc; ++g) {
            memset(hg, 0, (size_t)(d * B * C) * sizeof(double));
            for (int64_t i = bounds[g]; i < bounds[g + 1]; ++i) {
                const uint8_t *xr = Xb + rows[i] * d;
                if (is_cls) {
                    double wi = w[i];
                    int64_t c = ycls[i];
                    for (int64_t f = 0; f < d; ++f)
                        hg[(f * B + xr[f]) * C + c] += wi;
                } else {
                    double wi = w[i], yi = yreg[i];
                    double wy = wi * yi, wy2 = wi * (yi * yi);
                    for (int64_t f = 0; f < d; ++f) {
                        double *hb = hg + (f * B + xr[f]) * 3;
                        hb[0] += wi; hb[1] += wy; hb[2] += wy2;
                    }
                }
            }
            score_node(hg, d, B, C, is_cls, msl,
                       u ? u + g * d * B : 0, mask ? mask + g * d : 0,
                       bg_out + g, bf_out + g, bb_out + g, tot_out + g * C);
        }
    }
}

/* Partition split nodes' samples into [left block, right block] child order
 * (stable within a side), writing the next level's instance arrays at
 * cpos[g], plus per-child payload sums (class-weight rows for
 * classification, (Σw, Σwy) for regression) and left-child counts. */
void train_partition(const uint8_t *Xb, int64_t d,
                     const int64_t *rows, const double *w,
                     const int64_t *ycls, const double *yreg,
                     const int64_t *bounds, int64_t gc,
                     const uint8_t *split, const int64_t *bf,
                     const int64_t *bb, const int64_t *cpos,
                     int is_cls, int64_t Cv,
                     int64_t *rows_next, double *w_next,
                     int64_t *nl_out, double *csum)
{
    #pragma omp parallel for schedule(dynamic, 4)
    for (int64_t g = 0; g < gc; ++g) {
        nl_out[g] = 0;
        if (!split[g]) continue;
        int64_t s0 = bounds[g], s1 = bounds[g + 1];
        int64_t f = bf[g], b = bb[g];
        int64_t nl = 0;
        for (int64_t i = s0; i < s1; ++i)
            nl += (int64_t)(Xb[rows[i] * d + f] <= b);
        nl_out[g] = nl;
        int64_t li = cpos[g], ri = cpos[g] + nl;
        double *cs = csum + g * 2 * Cv;
        for (int64_t i = s0; i < s1; ++i) {
            int64_t r = rows[i];
            int go_left = Xb[r * d + f] <= b;
            int64_t o = go_left ? li++ : ri++;
            rows_next[o] = r; w_next[o] = w[i];
            double *c = cs + (go_left ? 0 : Cv);
            if (is_cls) c[ycls[i]] += w[i];
            else { c[0] += w[i]; c[1] += w[i] * yreg[i]; }
        }
    }
}

/* Dense proximity block: out[i,j] = Σ_t q[i,t] w[j,t] 1[gl_q[i,t]=gl_w[j,t]]. */
void prox_block(const int64_t *gl_q, const double *q, int64_t nq,
                const int64_t *gl_w, const double *w, int64_t nw,
                int64_t T, double *out)
{
    #pragma omp parallel for schedule(static)
    for (int64_t i = 0; i < nq; ++i) {
        const int64_t *gi = gl_q + i * T;
        const double *qi = q + i * T;
        double *o = out + i * nw;
        for (int64_t j = 0; j < nw; ++j) {
            const int64_t *gj = gl_w + j * T;
            const double *wj = w + j * T;
            double acc = 0.0;
            for (int64_t t = 0; t < T; ++t)
                if (gi[t] == gj[t]) acc += qi[t] * wj[t];
            o[j] = acc;
        }
    }
}
"""

_lib: Optional[ctypes.CDLL] = None
_tried = False
_tmpdir = None   # keeps a TemporaryDirectory alive if we fall back to it


def _build_dir() -> Path:
    d = Path(__file__).resolve().parent / "_native_build"
    try:
        d.mkdir(exist_ok=True)
        probe = d / ".probe"
        probe.write_text("")
        probe.unlink()
        return d
    except OSError:
        global _tmpdir
        _tmpdir = tempfile.TemporaryDirectory(prefix="repro_native_")
        return Path(_tmpdir.name)


def _compile() -> Optional[ctypes.CDLL]:
    import platform
    cc = os.environ.get("CC", "cc")
    # Key the cache on everything that shapes the binary: source, compiler,
    # flag candidates, and the CPU feature set (-march=native binaries must
    # not be reused across microarchitectures; /proc/cpuinfo flags identify
    # those where platform.machine() cannot).
    flag_sets = (["-O3", "-march=native", "-fopenmp"],
                 ["-O3", "-fopenmp"], ["-O3"])
    cpu = ""
    try:
        with open("/proc/cpuinfo") as fh:
            cpu = "".join(ln for ln in fh
                          if ln.startswith(("flags", "model name")))[:4096]
    except OSError:
        cpu = platform.processor() or ""
    key = "|".join([_SOURCE, cc, repr(flag_sets), platform.machine(), cpu])
    tag = hashlib.sha1(key.encode()).hexdigest()[:16]
    build = _build_dir()
    so_path = build / f"route_{tag}.so"
    if not so_path.exists():
        src_path = build / f"route_{tag}.c"
        src_path.write_text(_SOURCE)
        tmp_so = build / f".route_{tag}.{os.getpid()}.so"
        for flags in flag_sets:
            cmd = [cc, *flags, "-shared", "-fPIC", str(src_path),
                   "-o", str(tmp_so)]
            try:
                r = subprocess.run(cmd, capture_output=True, timeout=120)
            except (OSError, subprocess.TimeoutExpired):
                return None
            if r.returncode == 0:
                os.replace(tmp_so, so_path)   # atomic vs concurrent builders
                break
        else:
            return None
    try:
        lib = ctypes.CDLL(str(so_path))
    except OSError:
        return None
    lib.route_forest.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32)]
    lib.route_forest.restype = None
    pd = ctypes.POINTER(ctypes.c_double)
    pl = ctypes.POINTER(ctypes.c_int64)
    lib.prox_bucket.argtypes = [pl, pd, ctypes.c_int64, ctypes.c_int64,
                                pd, ctypes.c_int64, pd]
    lib.prox_bucket.restype = None
    lib.prox_gather.argtypes = [pl, pd, ctypes.c_int64, ctypes.c_int64,
                                pd, ctypes.c_int64, pd]
    lib.prox_gather.restype = None
    lib.prox_block.argtypes = [pl, pd, ctypes.c_int64,
                               pl, pd, ctypes.c_int64,
                               ctypes.c_int64, pd]
    lib.prox_block.restype = None
    pu8 = ctypes.POINTER(ctypes.c_uint8)
    i64 = ctypes.c_int64
    lib.train_hist.argtypes = [pu8, i64, pl, pd, pl, pd, pl, i64,
                               i64, i64, ctypes.c_int, i64, pd]
    lib.train_hist.restype = None
    lib.train_best_split.argtypes = [pd, i64, i64, i64, i64, ctypes.c_int,
                                     ctypes.c_double, pd, pu8,
                                     pd, pl, pl, pd]
    lib.train_best_split.restype = None
    lib.train_level.argtypes = [pu8, i64, pl, pd, pl, pd, pl, i64,
                                i64, i64, ctypes.c_int, ctypes.c_double,
                                pd, pu8, pd, pd, pl, pl, pd]
    lib.train_level.restype = None
    lib.max_threads.argtypes = []
    lib.max_threads.restype = i64
    lib.train_partition.argtypes = [pu8, i64, pl, pd, pl, pd, pl, i64,
                                    pu8, pl, pl, pl, ctypes.c_int, i64,
                                    pl, pd, pl, pd]
    lib.train_partition.restype = None
    return lib


def available() -> bool:
    global _lib, _tried
    if not _tried:
        _tried = True
        if os.environ.get("REPRO_DISABLE_NATIVE"):
            _lib = None
        else:
            try:
                _lib = _compile()
            except Exception:
                _lib = None
    return _lib is not None


# Fewer (row, tree) walks than this route on one thread: starting the
# thread team costs more than they do.  8-core CPU host, 100 trees of 64
# leaves (≈ 20 ns a walk): one thread won up to 400 walks (13.2 against
# 18.5 µs), eight threads from 800 on (14.6 against 18.8 µs); 100
# unbounded-depth trees gained from eight threads at 200 walks already
# (16.6 against 54.6 µs).  So the cut sits low: a few µs lost on shallow
# trees above it, tens of µs saved on deep ones.
_SERIAL_WALKS = 256
# A task's block of X rows is cut below this size, about a core's cache.
_ROW_BLOCK_BYTES = 1 << 20


def route_grain(n: int, n_trees: int, d: int,
                threads: int) -> tuple[int, int]:
    """(row_block, threads) of ``route_forest`` for ``n`` rows of width
    ``d`` through ``n_trees`` trees, given ``threads`` available.

    A task is one tree over a block of rows: the whole batch, unless its X
    rows pass ``_ROW_BLOCK_BYTES``, then equal blocks under it.  No more
    threads than tasks, and one below ``_SERIAL_WALKS`` walks."""
    n_blocks = max(1, -(-n * d * 8 // _ROW_BLOCK_BYTES))
    row_block = max(1, -(-n // n_blocks))
    if n * n_trees < _SERIAL_WALKS:
        return row_block, 1
    tasks = -(-n // row_block) * n_trees
    return row_block, max(1, min(threads, tasks))


def route_native(feature_f: np.ndarray, threshold_f: np.ndarray,
                 lr: np.ndarray, leaf_f: np.ndarray, n_trees: int,
                 max_nodes: int, X: np.ndarray,
                 threads: Optional[int] = None) -> tuple[np.ndarray, int]:
    """(N, T) int32 leaf ids and the number of threads that routed them;
    inputs are the TreeArrays.flat() arrays.  ``threads`` stands in for
    the host's OpenMP thread count (tests cross the tiling with it)."""
    assert available(), "native kernel unavailable; check available() first"
    X = np.ascontiguousarray(X, dtype=np.float64)
    n, d = X.shape
    out = np.empty((n, n_trees), dtype=np.int32)
    row_block, threads = route_grain(
        n, n_trees, d, int(_lib.max_threads()) if threads is None
        else threads)
    p = ctypes.POINTER(ctypes.c_double)
    pi = ctypes.POINTER(ctypes.c_int32)
    _lib.route_forest(
        X.ctypes.data_as(p), n, d,
        feature_f.ctypes.data_as(pi), threshold_f.ctypes.data_as(p),
        lr.ctypes.data_as(pi), leaf_f.ctypes.data_as(pi),
        n_trees, max_nodes, row_block, threads, out.ctypes.data_as(pi))
    return out, threads


# ---------------------------------------------------------------- proximity
def _pd(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def _pl(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _prep(gl: np.ndarray, wts: np.ndarray, V2: Optional[np.ndarray] = None):
    gl = np.ascontiguousarray(gl, dtype=np.int64)
    wts = np.ascontiguousarray(wts, dtype=np.float64)
    if V2 is None:
        return gl, wts
    return gl, wts, np.ascontiguousarray(V2, dtype=np.float64)


def prox_bucket_native(gl_w: np.ndarray, w: np.ndarray, V: np.ndarray,
                       total_leaves: int) -> np.ndarray:
    """(L, C) leaf bucket table s[l, c] = Σ_{(j,t): gl_w[j,t]=l} w[j,t] V[j,c]
    — the reference-side half of P V = Q (Wᵀ V), cacheable across queries."""
    assert available(), "native kernel unavailable; check available() first"
    gl_w, w, V = _prep(gl_w, w, V)
    nw, T = gl_w.shape
    C = V.shape[1]
    s = np.zeros((total_leaves, C), dtype=np.float64)
    _lib.prox_bucket(_pl(gl_w), _pd(w), nw, T, _pd(V), C, _pd(s))
    return s


def prox_gather_native(gl_q: np.ndarray, q: np.ndarray,
                       s: np.ndarray) -> np.ndarray:
    """(Nq, C) gather out[i, c] = Σ_t q[i,t] s[gl_q[i,t], c] — the query-side
    half; O(Nq·T·C), independent of the reference-set size."""
    assert available(), "native kernel unavailable; check available() first"
    gl_q, q = _prep(gl_q, q)
    s = np.ascontiguousarray(s, dtype=np.float64)
    nq, T = gl_q.shape
    C = s.shape[1]
    out = np.empty((nq, C), dtype=np.float64)
    _lib.prox_gather(_pl(gl_q), _pd(q), nq, T, _pd(s), C, _pd(out))
    return out


def prox_matmat_native(gl_q: np.ndarray, q: np.ndarray, gl_w: np.ndarray,
                       w: np.ndarray, V: np.ndarray,
                       total_leaves: int) -> np.ndarray:
    """(P V) through the factors: bucket then gather, all in C."""
    s = prox_bucket_native(gl_w, w, V, total_leaves)
    return prox_gather_native(gl_q, q, s)


def prox_block_native(gl_q: np.ndarray, q: np.ndarray, gl_w: np.ndarray,
                      w: np.ndarray) -> np.ndarray:
    """Dense (Nq, Nw) proximity block P[i,j] = Σ_t q[i,t] w[j,t]
    1[gl_q[i,t] = gl_w[j,t]]."""
    assert available(), "native kernel unavailable; check available() first"
    gl_q, q = _prep(gl_q, q)
    gl_w, w = _prep(gl_w, w)
    nq, T = gl_q.shape
    nw = gl_w.shape[0]
    out = np.empty((nq, nw), dtype=np.float64)
    _lib.prox_block(_pl(gl_q), _pd(q), nq, _pl(gl_w), _pd(w), nw, T, _pd(out))
    return out


# ---------------------------------------------------------------- training
def _pu8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _hist_stripes(gc: int, d: int) -> int:
    """Feature stripes per node: 1 when the node count alone saturates the
    threads, otherwise split each node's features so (gc × stripes) does.
    Striping never changes results — each (node, stripe) is owned by one
    thread walking samples in order."""
    ncpu = os.cpu_count() or 1
    if gc >= 2 * ncpu:
        return 1
    return max(1, min(d, (4 * ncpu + gc - 1) // max(gc, 1)))


def train_hist_native(Xb_u8: np.ndarray, rows: np.ndarray, w: np.ndarray,
                      y_inst: np.ndarray, bounds: np.ndarray, B: int, C: int,
                      cls: bool) -> np.ndarray:
    """(gc, d, B, C) float64 histograms for one chunk of active nodes.

    ``Xb_u8`` is the full (n, d) uint8 code matrix; ``rows``/``w``/``y_inst``
    are per-instance arrays sorted by node with ranges in ``bounds``.
    Bit-identical to the numpy tiled-bincount path (per-bin accumulation in
    sample order)."""
    assert available(), "native kernel unavailable; check available() first"
    gc = len(bounds) - 1
    n, d = Xb_u8.shape
    hist = np.zeros((gc, d, B, C), dtype=np.float64)
    if gc == 0 or len(rows) == 0:
        return hist
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    w = np.ascontiguousarray(w, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.int64)
    if cls:
        yc = np.ascontiguousarray(y_inst, dtype=np.int64)
        yc_p, yr_p = _pl(yc), None
    else:
        yr = np.ascontiguousarray(y_inst, dtype=np.float64)
        yc_p, yr_p = None, _pd(yr)
    _lib.train_hist(_pu8(Xb_u8), d, _pl(rows), _pd(w), yc_p, yr_p,
                    _pl(bounds), gc, B, C, int(cls), _hist_stripes(gc, d),
                    _pd(hist))
    return hist


def train_best_split_native(hist: np.ndarray, msl: float, cls: bool,
                            u: Optional[np.ndarray],
                            mask: Optional[np.ndarray]):
    """Best (feature, bin) split per node; returns (gain, f, b, node_tot).

    Mirrors the numpy ``_best_splits`` operation order exactly (float64,
    first-maximum tie-breaks); ``u``/``mask`` are the Python-side RNG draws
    so both backends consume identical streams."""
    assert available(), "native kernel unavailable; check available() first"
    gc, d, B, C = hist.shape
    hist = np.ascontiguousarray(hist, dtype=np.float64)
    bg = np.empty(gc, dtype=np.float64)
    bf = np.empty(gc, dtype=np.int64)
    bb = np.empty(gc, dtype=np.int64)
    tot = np.zeros((gc, C), dtype=np.float64)
    u_c = np.ascontiguousarray(u, dtype=np.float64) if u is not None else None
    m_c = np.ascontiguousarray(mask, dtype=np.uint8) if mask is not None \
        else None
    _lib.train_best_split(_pd(hist), gc, d, B, C, int(cls), float(msl),
                          _pd(u_c) if u_c is not None else None,
                          _pu8(m_c) if m_c is not None else None,
                          _pd(bg), _pl(bf), _pl(bb), _pd(tot))
    return bg, bf, bb, tot


def train_level_native(Xb_u8: np.ndarray, rows: np.ndarray, w: np.ndarray,
                       y_inst: np.ndarray, bounds: np.ndarray, B: int,
                       C: int, cls: bool, msl: float,
                       u: Optional[np.ndarray], mask: Optional[np.ndarray]):
    """Histogram + best-split for one chunk of active nodes, fused.

    Wide node sets use the fused per-node kernel (one cache-resident scratch
    histogram per thread — no (gc, d, B, C) buffer is ever materialized);
    narrow node sets fall back to the two-phase striped kernels so a single
    big node still gets intra-node parallelism.  Results are bit-identical
    either way.  Returns (gain, feature, bin, node_tot)."""
    assert available(), "native kernel unavailable; check available() first"
    gc = len(bounds) - 1
    n, d = Xb_u8.shape
    if gc < 2 * (os.cpu_count() or 1):
        hist = train_hist_native(Xb_u8, rows, w, y_inst, bounds, B, C, cls)
        return train_best_split_native(hist, msl, cls, u, mask)
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    w = np.ascontiguousarray(w, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.int64)
    if cls:
        yc = np.ascontiguousarray(y_inst, dtype=np.int64)
        yc_p, yr_p = _pl(yc), None
    else:
        yr = np.ascontiguousarray(y_inst, dtype=np.float64)
        yc_p, yr_p = None, _pd(yr)
    bg = np.empty(gc, dtype=np.float64)
    bf = np.empty(gc, dtype=np.int64)
    bb = np.empty(gc, dtype=np.int64)
    tot = np.zeros((gc, C), dtype=np.float64)
    u_c = np.ascontiguousarray(u, dtype=np.float64) if u is not None else None
    m_c = np.ascontiguousarray(mask, dtype=np.uint8) if mask is not None \
        else None
    # per-thread scratch histograms, numpy-allocated so exhaustion raises
    # MemoryError instead of a NULL dereference inside the kernel
    scratch = np.empty((int(_lib.max_threads()), d * B * C), dtype=np.float64)
    _lib.train_level(_pu8(Xb_u8), d, _pl(rows), _pd(w), yc_p, yr_p,
                     _pl(bounds), gc, B, C, int(cls), float(msl),
                     _pd(u_c) if u_c is not None else None,
                     _pu8(m_c) if m_c is not None else None, _pd(scratch),
                     _pd(bg), _pl(bf), _pl(bb), _pd(tot))
    return bg, bf, bb, tot


def train_partition_native(Xb_u8: np.ndarray, rows: np.ndarray,
                           w: np.ndarray, y_inst: np.ndarray,
                           bounds: np.ndarray, split: np.ndarray,
                           best_f: np.ndarray, best_b: np.ndarray,
                           cpos: np.ndarray, m_next: int, cls: bool,
                           Cv: int):
    """Partition split nodes' samples into [left, right] child order.

    Returns (rows_next, w_next, child_counts, csum) exactly like the numpy
    partition (stable within a side, per-child payload sums accumulated in
    sample order)."""
    assert available(), "native kernel unavailable; check available() first"
    gc = len(bounds) - 1
    d = Xb_u8.shape[1]
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    w = np.ascontiguousarray(w, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.int64)
    split_u8 = np.ascontiguousarray(split, dtype=np.uint8)
    bf = np.ascontiguousarray(best_f, dtype=np.int64)
    bb = np.ascontiguousarray(best_b, dtype=np.int64)
    cpos = np.ascontiguousarray(cpos, dtype=np.int64)
    if cls:
        yc = np.ascontiguousarray(y_inst, dtype=np.int64)
        yc_p, yr_p = _pl(yc), None
    else:
        yr = np.ascontiguousarray(y_inst, dtype=np.float64)
        yc_p, yr_p = None, _pd(yr)
    rows_next = np.empty(m_next, dtype=np.int64)
    w_next = np.empty(m_next, dtype=np.float64)
    nl = np.zeros(gc, dtype=np.int64)
    csum = np.zeros((gc, 2, Cv), dtype=np.float64)
    _lib.train_partition(_pu8(Xb_u8), d, _pl(rows), _pd(w), yc_p, yr_p,
                         _pl(bounds), gc, _pu8(split_u8), _pl(bf), _pl(bb),
                         _pl(cpos), int(cls), Cv,
                         _pl(rows_next), _pd(w_next), _pl(nl), _pd(csum))
    spl = split.astype(bool)
    counts = np.diff(bounds)
    ns = int(spl.sum())
    child_counts = np.empty(2 * ns, dtype=np.int64)
    child_counts[0::2] = nl[spl]
    child_counts[1::2] = counts[spl] - nl[spl]
    return rows_next, w_next, child_counts, csum[spl].reshape(-1, Cv)
