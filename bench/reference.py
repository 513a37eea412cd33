"""Plain reference of the pooled-memory simulator, for the ``correct`` check.

A straightforward, sequential implementation of the simulated system the
configurations describe (arXiv:2406.14778, Sec. III-V): per node a
set-associative LRU DRAM cache of sub-page blocks in front of the FAM pool,
an SPP prefetcher, a prefetch queue, a stride core prefetcher with its fill
buffer, and a token-bucket MIMD rate controller; one FAM controller per
system that serves the step's demand and prefetch requests FIFO or by
fluid two-class WFQ. One LLC-miss event per node per step; the core model
is analytic (cycles = gaps + stall / MLP).

It imports nothing of the program and takes nothing the program made: the
workload model is ``bench/workloads.json``, the system is the configuration
file, and the traces are drawn here from the seed with ``jax.random`` on the
host CPU. It runs one event at a time in Python, with every floating-point
value held in ``dtype`` (float32, the precision the configurations state;
the control runs it in bfloat16).
"""
from __future__ import annotations

import math
import zlib
from functools import lru_cache
from typing import Dict, List, Tuple

import numpy as np

from grid import BENCH, load_json

U32 = 0xFFFFFFFF
PAGE_BITS = 12
INT32_MAX = 2**31 - 1
METRICS = ("ipc", "fam_latency", "demand_hit_fraction",
           "corepf_hit_fraction", "prefetches_issued", "issue_rate",
           "cache_occupancy")


# ---------------------------------------------------------------------------
# Traces
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _model() -> dict:
    return load_json(BENCH / "workloads.json")


def trace_seed(name: str, seed: int) -> int:
    return zlib.crc32(f"{name}:{seed}".encode())


def node_seed(seed: int, node: int) -> int:
    return seed + 1_000_003 * node


@lru_cache(maxsize=None)
def _head_cdf(a: float, head: int) -> np.ndarray:
    k = np.arange(1, 100_001, dtype=np.float64)
    zeta = float(np.sum(k ** -a) + 100_000 ** (1.0 - a) / (a - 1.0))
    kk = np.arange(1, head + 1, dtype=np.float64)
    return (np.cumsum(kk ** -a) / zeta).astype(np.float32)


@lru_cache(maxsize=None)
def _draws(T: int, K: int, streams_max: int, head: int, sigma: float):
    """The random draws of one node trace and the float arithmetic on them,
    as one jitted function on the default device (its transcendentals then
    round as the device rounds them)."""
    import jax
    import jax.numpy as jnp

    def f(key, n, lo_tile, hi_tile, zipf_a, head_mass, mean_gap):
        sub = lambda i: jax.random.fold_in(key, i)
        raw = jax.random.randint(sub(0), (T,), 0, 1 << 30)
        u = jax.random.uniform(sub(1), (T,))
        uni = jax.random.randint(sub(2), (T,), 0, n)
        starts = jax.random.randint(sub(3), (streams_max,), 0, n)
        bases = jax.random.randint(sub(4), (K,), 0, jnp.maximum(n - hi_tile,
                                                                 1))
        spans = jax.random.randint(sub(5), (K,), lo_tile, hi_tile)
        a1 = jnp.maximum(zipf_a, 1.01) - 1.0
        v = jnp.clip((u - head_mass) / jnp.maximum(1.0 - head_mass, 1e-9),
                     1e-9, 1.0)
        log_max = jnp.log(jnp.float32(INT32_MAX))
        log_tail = jnp.log(head + 0.5) - jnp.log(v) / a1
        tail = jnp.exp(jnp.minimum(log_tail, log_max))
        overflow = log_tail >= log_max
        tail = jnp.floor(jnp.where(overflow, 0.0, tail)).astype(jnp.int32)
        gaps = jnp.exp(jax.random.normal(sub(6), (T,)) * sigma) * mean_gap
        return raw, u, uni, starts, bases, spans, tail, overflow, gaps

    return jax.jit(f)


def node_trace(name: str, seed: int, T: int, base_ipc: float = 2.0
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(addr_bytes int64 (T,), gap_cycles float32 (T,)) of one node."""
    import jax
    import jax.numpy as jnp

    m = _model()
    c = m["constants"]
    spec = m["workloads"][name]
    line = c["line_bytes"]
    n = max(int(spec["footprint_mb"] * (1 << 20) // line), 1 << 12)
    pat = m["pattern_ids"][spec["pattern"]]
    tile = max(spec["tile_kb"] * 1024 // line, c["min_tile_lines"])
    head = c["zipf_head"]
    cdf = _head_cdf(float(spec["zipf_a"]), head) if spec["zipf_a"] > 1.0 \
        else np.ones(head, np.float32)
    mean_gap = np.float32((1000.0 / spec["mpki"]) / base_ipc)
    fn = _draws(T, T // (c["min_tile_lines"] // 2) + 2, c["streams_max"],
                head, c["gap_sigma"])
    out = fn(jnp.asarray([0, trace_seed(name, seed)], jnp.uint32),
             jnp.int32(n), jnp.int32(tile // 2), jnp.int32(tile),
             jnp.float32(spec["zipf_a"]), jnp.float32(cdf[-1]),
             jnp.float32(mean_gap))
    raw, u, uni, starts, bases, spans, tail, overflow, gaps = \
        (np.asarray(x) for x in jax.device_get(out))
    raw = raw.astype(np.int64)
    uni = uni.astype(np.int64)

    # streams: the k-th visit of stream s reads starts[s] + k * stride
    pick = raw % spec["streams"]
    occ = np.zeros(T, np.int64)
    seen: Dict[int, int] = {}
    for i, s in enumerate(pick.tolist()):
        occ[i] = seen.get(s, 0)
        seen[s] = occ[i] + 1
    s_lines = (starts.astype(np.int64)[pick] + occ * spec["stride"]) % n

    # tiles: back-to-back segments, each a row-major sweep from its base
    seg_start = np.concatenate([[0], np.cumsum(spans.astype(np.int64))[:-1]])
    pos = np.arange(T)
    seg = np.searchsorted(seg_start, pos, side="right") - 1
    off = pos - seg_start[seg]
    jit = c["tile_jitter"]
    jitter = (raw >> 3) % (2 * jit + 1) - jit
    t_lines = np.clip(bases.astype(np.int64)[seg] + off % tile + jitter,
                      0, n - 1)

    # zipf: exact head, continuous power-law tail past it (ranks that
    # overflow int32 read a uniform line); weak skew: a hot region
    in_head = u <= cdf[-1]
    head_rank = np.searchsorted(cdf, u, side="right") + 1
    strong = np.where(in_head, head_rank, tail.astype(np.int64))
    overflow = overflow & ~in_head
    hot = uni % max(n // c["hot_region_div"], 1)
    hot_p = np.float32(min(max(spec["zipf_a"] * 0.5, 0.0), 1.0))
    weak = np.where(u < hot_p, hot, uni)
    is_strong = spec["zipf_a"] > 1.0
    rank = (strong if is_strong else weak) % n
    hashed = ((rank * c["addr_hash"]) & U32) % n
    z_lines = np.where(overflow, uni, hashed) if is_strong else hashed

    take_seq = ((raw >> 6) & 1023).astype(np.float32) * \
        np.float32(1.0 / 1024.0) < np.float32(spec["seq_frac"])
    m_lines = np.where(take_seq, s_lines, z_lines)
    lines = {0: s_lines, 1: s_lines, 2: t_lines, 3: z_lines}.get(pat,
                                                                 m_lines)
    return lines.astype(np.int64) * line, gaps.astype(np.float32)


# ---------------------------------------------------------------------------
# The simulated system
# ---------------------------------------------------------------------------

class _Node:
    """One compute node's state; every float is held in ``F``."""

    def __init__(self, st: dict, F):
        zero = F(0.0)
        self.clock = zero
        ST, PT = st["spp_signature_entries"], st["spp_pattern_entries"]
        self.st_tag = [0] * ST
        self.st_last = [0] * ST
        self.st_sig = [0] * ST
        self.pt_delta = [[0] * 4 for _ in range(PT)]
        self.pt_weight = [[0] * 4 for _ in range(PT)]
        self.pt_sigw = [0] * PT
        self.sets = st["num_sets"]
        self.ways = st["cache_ways"]
        self.tags: Dict[int, List[int]] = {}
        self.lru: Dict[int, List[int]] = {}
        self.stamp = 0
        self.q_block = [0] * st["prefetch_queue"]
        self.q_fin = [zero] * st["prefetch_queue"]
        self.issue_rate = F(1.0)
        self.tokens = zero
        self.min_latency = st["unloaded"]
        self.lat_sum = zero
        self.lat_cnt = zero
        self.lat_ema = zero
        self.thr_issued = zero
        self.thr_useful = zero
        self.acc_ema = F(0.5)
        self.events = 0
        self.core_last = -1
        self.core_stride = 0
        self.buf_line = [0] * st["core_fill_entries"]
        self.buf_fin = [zero] * st["core_fill_entries"]
        self.buf_ptr = 0
        self.instr = zero
        self.cycles = zero
        self.fam_lat_sum = zero
        self.fam_cnt = zero
        self.demand_fam = zero
        self.demand_hit = zero
        self.corepf_fam = zero
        self.corepf_hit = zero
        self.pf_issued = zero

    # -- DRAM cache (set-associative LRU, tags hold block + 1) -------------
    def _set(self, block: int) -> int:
        return ((((block & U32) * 0x9E3779B1) & U32) >> 7) % self.sets

    def _row(self, si: int):
        row = self.tags.get(si)
        if row is None:
            row = self.tags[si] = [0] * self.ways
            self.lru[si] = [0] * self.ways
        return row

    def lookup(self, block: int) -> Tuple[bool, int, int]:
        si = self._set(block)
        row = self.tags.get(si)
        tag = block + 1
        if row is None:
            return tag == 0, si, 0
        for w, t in enumerate(row):
            if t == tag:
                return True, si, w
        return False, si, 0

    def insert(self, block: int) -> None:
        si = self._set(block)
        row = self._row(si)
        lru = self.lru[si]
        tag = block + 1
        self.stamp += 1
        if tag in row:
            way = row.index(tag)
        elif 0 in row:
            way = row.index(0)
        else:
            way = lru.index(min(lru))
        row[way] = tag
        lru[way] = self.stamp

    def occupancy_count(self) -> int:
        return sum(1 for row in self.tags.values() for t in row if t > 0)


class System:
    """One simulated system: ``N`` nodes sharing one FAM controller."""

    def __init__(self, s: dict, dtype=np.float32):
        F = self.F = dtype
        sy = s["system"]
        fl = s["flags"]
        st = dict(sy)
        bb = int(sy["block_bytes"]).bit_length() - 1
        st["num_sets"] = max(1, sy["dram_cache_bytes"] // sy["block_bytes"]
                             // sy["cache_ways"])
        self.bb = bb
        self.bpp = 1 << (PAGE_BITS - bb)
        pol = s["policy"]
        par = s["params"]
        use_wfq = pol.get("scheduler") == "wfq" if s["explicit_policy"] \
            else bool(fl["wfq"])
        self.use_wfq = use_wfq
        weight = par.get("scheduler", {}).get(
            "weight", float(fl["wfq_weight"]) if not s["explicit_policy"]
            else float(sy["wfq_weight"]))
        self.W = F(weight)
        self.backlog_cap = F(par.get("scheduler", {}).get(
            "backlog_cap", sy["wfq_backlog_cap"]))
        self.threshold = F(par.get("prefetch", {}).get(
            "confidence_threshold", sy["spp_confidence_threshold"]))
        ad = par.get("adaptation", {})
        self.sample_interval = int(ad.get("sample_interval",
                                          sy["sample_interval"]))
        self.noise = F(ad.get("latency_noise_threshold",
                              sy["latency_noise_threshold"]))
        self.mimd = F(ad.get("mimd_increase", sy["mimd_increase"]))
        self.alpha = F(ad.get("ema_alpha", sy["ema_alpha"]))
        self.min_rate = F(ad.get("min_issue_rate", sy["min_issue_rate"]))
        self.core_pf = bool(fl["core_prefetch"])
        self.dram_pf = bool(fl["dram_prefetch"])
        self.bw_adapt = bool(fl["bw_adapt"])
        self.all_local = bool(fl["all_local"])
        self.alloc = int(sy["allocation_ratio"])
        self.D = sy["prefetch_degree"]
        self.CPF = sy["core_pf_degree"]
        self.C = sy["completions_per_step"]
        self.sig_mask = (1 << sy["spp_signature_bits"]) - 1
        self.cores = F(sy["cores_per_node"])
        self.base_ipc = F(sy["base_ipc"])
        self.mlp_cores = F(sy["mlp"]) * self.cores
        self.llc = F(sy["llc_latency"])
        self.local = F(sy["local_mem_latency"])
        fam_lat = F(sy["fam_mem_latency"])
        cxl = F(int(sy["cxl_min_latency_ns"] * sy["clock_ghz"]))
        self.cpb = F(1.0 / (sy["fam_bw_gbps"] / sy["clock_ghz"]))
        self.demand_bytes = F(sy["demand_bytes"])
        self.block_bytes = F(sy["block_bytes"])
        self.lat_fixed = fam_lat + cxl
        st["unloaded"] = fam_lat + cxl + self.cpb * self.demand_bytes
        self.q_cap = int(0.95 * sy["prefetch_queue"])
        self.fill_entries = sy["core_fill_entries"]
        self.nodes = [_Node(st, F) for _ in s["workloads"]]
        self.busy = [F(0.0), F(0.0)]
        self.one = F(1.0)
        self.zero = F(0.0)

    def _is_fam(self, page: int) -> bool:
        h = ((page & U32) * 0x61C88647 & U32) >> 16
        return h % (self.alloc + 1) != 0

    # -- SPP -----------------------------------------------------------------
    def _spp_train(self, nd: _Node, page: int, block: int, en: bool) -> int:
        ST = len(nd.st_tag)
        idx = ((((page & U32) * 0x9E3779B1) & U32) >> 8) % ST
        tag = page + 1
        hit = nd.st_tag[idx] == tag
        delta = block - nd.st_last[idx]
        old_sig = nd.st_sig[idx]
        mask = self.sig_mask
        if hit and delta != 0 and en:
            pi = old_sig % len(nd.pt_sigw)
            rd, rw = nd.pt_delta[pi], nd.pt_weight[pi]
            way = -1
            for w in range(4):
                if rd[w] == delta and rw[w] > 0:
                    way = w
                    break
            if way >= 0:
                rw[way] = min(rw[way] + 1, 15)
            else:
                way = rw.index(min(rw))
                rw[way] = 1
            rd[way] = delta
            if nd.pt_sigw[pi] < 60:
                nd.pt_sigw[pi] += 1
        new_sig = ((old_sig << 4) ^ (delta & mask)) & mask if hit \
            else block & mask
        if en:
            nd.st_tag[idx] = tag
            nd.st_last[idx] = block
            nd.st_sig[idx] = new_sig
        return new_sig

    def _spp_predict(self, nd: _Node, page: int, block: int, sig: int
                     ) -> List[Tuple[int, bool]]:
        F = self.F
        mask = self.sig_mask
        four = F(4.0)
        conf = self.one
        out = []
        alive = True
        for _ in range(self.D):
            if not alive:
                out.append((page * self.bpp, False))
                continue
            pi = sig % len(nd.pt_sigw)
            rw, rd = nd.pt_weight[pi], nd.pt_delta[pi]
            way = rw.index(max(rw))
            w = rw[way]
            sigw = max(nd.pt_sigw[pi], 1)
            step = F(F(w) / F(sigw))
            new_conf = F(conf * min(F(step * four), self.one))
            delta = rd[way]
            nb = block + delta
            ok = (w > 0 and new_conf >= self.threshold and 0 <= nb < self.bpp
                  and delta != 0)
            if ok:
                sig = ((sig << 4) ^ (delta & mask)) & mask
                block = nb
                conf = new_conf
                out.append((page * self.bpp + nb, True))
            else:
                alive = False
                out.append((page * self.bpp, False))
        return out

    # -- FAM controller ------------------------------------------------------
    def _chain(self, arr, srv, valid, busy0):
        """Busy chain: each valid request starts at max(arrival, busy)."""
        F = self.F
        cs = self.zero
        m = -math.inf
        fin = [self.zero] * len(arr)
        new_busy = busy0
        for i in range(len(arr)):
            if not valid[i]:
                continue
            cs = F(cs + srv[i])
            m = max(m, F(arr[i] - F(cs - srv[i])))
            base = max(m, busy0)
            fin[i] = F(cs + base)
            new_busy = max(new_busy, fin[i])
        return fin, new_busy

    def _arbitrate(self, d_arr, d_valid, p_arr, p_valid, p_bytes):
        F = self.F
        d_srv = F(self.cpb * self.demand_bytes)
        p_srv = [F(self.cpb * b) for b in p_bytes]
        if self.use_wfq:
            W = self.W
            d_busy0, p_busy0 = self.busy
            fd = F(F(W + self.one) / W)
            ds = [F(d_srv * (fd if p_busy0 > a else self.one)) for a in d_arr]
            d_fin, d_busy = self._chain(d_arr, ds, d_valid, d_busy0)
            fp = F(W + self.one)
            ps = [F(s * (fp if d_busy0 > a else self.one))
                  for s, a in zip(p_srv, p_arr)]
            p_fin, p_busy = self._chain(p_arr, ps, p_valid, p_busy0)
            self.busy = [d_busy, p_busy]
        else:
            arr = list(d_arr) + list(p_arr)
            srv = [d_srv] * len(d_arr) + p_srv
            val = list(d_valid) + list(p_valid)
            order = sorted(range(len(arr)),
                           key=lambda k: (arr[k] if val[k] else math.inf, k))
            fin_o, busy = self._chain([arr[k] for k in order],
                                      [srv[k] for k in order],
                                      [val[k] for k in order], self.busy[0])
            fin = [self.zero] * len(arr)
            for j, k in enumerate(order):
                fin[k] = fin_o[j]
            d_fin, p_fin = fin[:len(d_arr)], fin[len(d_arr):]
            self.busy = [busy, busy]
        d_fin = [F(f + self.lat_fixed) if v else self.zero
                 for f, v in zip(d_fin, d_valid)]
        p_fin = [F(f + self.lat_fixed) if v else self.zero
                 for f, v in zip(p_fin, p_valid)]
        return d_fin, p_fin

    # -- one event per node --------------------------------------------------
    def _phase_a(self, nd: _Node, addr: int, gap) -> dict:
        F = self.F
        bb, bpp = self.bb, self.bpp
        clock = F(nd.clock + gap)
        # retire up to C completed prefetches, earliest first, into the cache
        done = sorted((nd.q_fin[i], i) for i, b in enumerate(nd.q_block)
                      if b > 0 and nd.q_fin[i] <= clock)[:self.C]
        fills = []
        for _, i in done:
            fills.append(nd.q_block[i] - 1)
            nd.q_block[i] = 0

        page = addr >> PAGE_BITS
        bip = (addr >> bb) & (bpp - 1)
        gblock = addr >> bb
        is_fam = self._is_fam(page) and not self.all_local
        line = addr >> 6
        cpb_hit = False
        cpb_fin = self.zero
        for j, bl in enumerate(nd.buf_line):
            if bl == line + 1:
                cpb_hit = True
                cpb_fin = max(cpb_fin, nd.buf_fin[j])
        cpb_hit = cpb_hit and self.core_pf

        sig = self._spp_train(nd, page, bip, is_fam and self.dram_pf)
        cands = self._spp_predict(nd, page, bip, sig)

        stride = line - nd.core_last
        stride_ok = (stride == nd.core_stride and stride != 0
                     and abs(stride) < 32)
        cpf_lines = [line + stride * (1 + k) for k in range(self.CPF)]
        cpf_valid = [stride_ok and self._is_fam(cl >> (PAGE_BITS - 6))
                     and not self.all_local and self.core_pf
                     for cl in cpf_lines]
        cpf_blocks = [cl >> (bb - 6) for cl in cpf_lines]

        for b in fills:
            nd.insert(b)
        raw, si, way = nd.lookup(gblock)
        hit = raw and is_fam and self.dram_pf
        if hit:
            nd.stamp += 1
            nd.lru[si][way] = nd.stamp
        cand_hit = [nd.lookup(b)[0] for b, _ in cands]
        cpf_raw = [nd.lookup(b)[0] for b in cpf_blocks]

        match = [nd.q_fin[i] for i, b in enumerate(nd.q_block)
                 if b == gblock + 1]
        inflight = bool(match) and is_fam and not hit and self.dram_pf
        inflight_fin = max(match) if match else self.zero
        if match:
            inflight_fin = max(inflight_fin, self.zero)
        hit = hit and not cpb_hit
        inflight = inflight and not cpb_hit
        to_fam = is_fam and not hit and not inflight and not cpb_hit

        qset = set(nd.q_block)
        pf_valid = [v and not h and (b + 1) not in qset and is_fam
                    and self.dram_pf
                    for (b, v), h in zip(cands, cand_hit)]
        want = sum(pf_valid)
        if self.bw_adapt:
            tokens = min(F(nd.tokens + F(nd.issue_rate * F(max(want, 1)))),
                         F(8.0))
            grant = min(want, int(math.floor(tokens)))
            nd.tokens = F(tokens - F(grant))
        else:
            grant = want
        free = sum(1 for b in nd.q_block if b == 0)
        kept, rank = [], 0
        for v in pf_valid:
            rank += v
            kept.append(v and rank <= grant)
        pf_valid, rank = [], 0
        for v in kept:
            rank += v
            pf_valid.append(v and rank <= free)
        cpf_hits = [h and self.dram_pf for h in cpf_raw]
        cpf_to_fam = [v and not h for v, h in zip(cpf_valid, cpf_hits)]

        nd.clock = clock
        nd.core_last = line
        if stride != 0:
            nd.core_stride = stride
        return dict(is_fam=is_fam, hit=hit, inflight=inflight,
                    inflight_fin=inflight_fin, to_fam=to_fam,
                    cpb_hit=cpb_hit, cpb_fin=cpb_fin,
                    pf_blocks=[b for b, _ in cands], pf_valid=pf_valid,
                    cpf_lines=cpf_lines, cpf_valid=cpf_valid,
                    cpf_hits=[h and v for h, v in zip(cpf_hits, cpf_valid)],
                    cpf_to_fam=cpf_to_fam, gap=gap)

    def _phase_c(self, nd: _Node, r: dict, d_fin, pf_fin, cpf_fin,
                 warm: bool) -> None:
        F = self.F
        clock = nd.clock
        if r["cpb_hit"]:
            lat = max(F(r["cpb_fin"] - clock), self.llc)
        elif not r["is_fam"] or r["hit"]:
            lat = self.local
        elif r["inflight"]:
            lat = max(F(r["inflight_fin"] - clock), self.local)
        else:
            lat = max(F(d_fin - clock), self.one)

        for b, f, v in zip(r["pf_blocks"], pf_fin, r["pf_valid"]):
            if not v:
                continue
            free = [i for i, x in enumerate(nd.q_block) if x == 0]
            if free and len(nd.q_block) - len(free) < self.q_cap:
                nd.q_block[free[0]] = b + 1
                nd.q_fin[free[0]] = f
        fam_miss = r["is_fam"] and not r["hit"] and not r["inflight"]
        cached_fin = F(clock + self.local)
        for cl, f, v, h in zip(r["cpf_lines"], cpf_fin, r["cpf_valid"],
                               r["cpf_hits"]):
            if v:
                nd.buf_line[nd.buf_ptr] = cl + 1
                nd.buf_fin[nd.buf_ptr] = cached_fin if h else f
                nd.buf_ptr = (nd.buf_ptr + 1) % self.fill_entries
        npf = sum(r["pf_valid"])

        # rate controller: observe every event, adapt once per sample cycle
        if fam_miss:
            nd.lat_sum = F(nd.lat_sum + lat)
            nd.lat_cnt = F(nd.lat_cnt + self.one)
        if r["hit"]:
            nd.thr_useful = F(nd.thr_useful + self.one)
        nd.thr_issued = F(nd.thr_issued + F(npf))
        nd.events += 1
        if self.bw_adapt and nd.events >= self.sample_interval:
            self._adapt(nd)

        stall = F(lat / self.mlp_cores)
        nd.clock = F(clock + stall)
        if warm:
            gap = r["gap"]
            nd.instr = F(nd.instr + F(gap * self.base_ipc))
            nd.cycles = F(nd.cycles + F(gap + stall))
            if r["is_fam"]:
                nd.fam_lat_sum = F(nd.fam_lat_sum + lat)
                nd.fam_cnt = F(nd.fam_cnt + self.one)
                nd.demand_fam = F(nd.demand_fam + self.one)
            if r["hit"]:
                nd.demand_hit = F(nd.demand_hit + self.one)
            nd.corepf_fam = F(nd.corepf_fam + F(sum(r["cpf_valid"])))
            nd.corepf_hit = F(nd.corepf_hit + F(sum(r["cpf_hits"])))
            nd.pf_issued = F(nd.pf_issued + F(npf))

    def _adapt(self, nd: _Node) -> None:
        F = self.F
        one, a = self.one, self.alpha
        avg = F(nd.lat_sum / max(nd.lat_cnt, one))
        ema = avg if nd.lat_ema == self.zero else \
            F(F(F(one - a) * nd.lat_ema) + F(a * avg))
        min_lat = min(nd.min_latency, ema)
        acc = F(nd.thr_useful / max(nd.thr_issued, one))
        acc_ema = F(F(F(one - a) * nd.acc_ema) + F(a * acc))
        thresh = F(self.noise * min_lat)
        half = F(0.5)
        if ema > thresh:
            excess = min(max(F(F(ema - thresh) / max(thresh, one)),
                             self.zero), one)
            dec = F(one - F(F(half * excess) * F(one - F(half * acc_ema))))
            rate = F(nd.issue_rate * dec)
        else:
            rate = F(nd.issue_rate * self.mimd)
        nd.issue_rate = min(max(rate, self.min_rate), one)
        nd.min_latency = min_lat
        nd.lat_sum = nd.lat_cnt = self.zero
        nd.lat_ema = ema
        nd.thr_issued = nd.thr_useful = self.zero
        nd.acc_ema = acc_ema
        nd.events = 0

    def run(self, addrs: np.ndarray, gaps: np.ndarray, warm_start: int
            ) -> Dict[str, np.ndarray]:
        F = self.F
        N, T = addrs.shape
        D, CPF = self.D, self.CPF
        addr_l = addrs.tolist()
        gap_l = [[F(F(g) / self.cores) for g in row.tolist()]
                 for row in gaps.astype(np.float32)]
        dbytes = [self.block_bytes] * (N * D) + [self.demand_bytes] * \
            (N * CPF)
        for t in range(T):
            reqs = [self._phase_a(nd, addr_l[n][t], gap_l[n][t])
                    for n, nd in enumerate(self.nodes)]
            if self.use_wfq:
                for nd, r in zip(self.nodes, reqs):
                    if not F(self.busy[1] - nd.clock) < self.backlog_cap:
                        r["pf_valid"] = [False] * D
                        r["cpf_to_fam"] = [False] * CPF
            clocks = [nd.clock for nd in self.nodes]
            p_arr = [c for c in clocks for _ in range(D)] + \
                [c for c in clocks for _ in range(CPF)]
            p_valid = [v for r in reqs for v in r["pf_valid"]] + \
                [v for r in reqs for v in r["cpf_to_fam"]]
            d_fin, p_fin = self._arbitrate(clocks, [r["to_fam"] for r in reqs],
                                           p_arr, p_valid, dbytes)
            warm = t >= warm_start
            for n, (nd, r) in enumerate(zip(self.nodes, reqs)):
                self._phase_c(nd, r, d_fin[n], p_fin[n * D:(n + 1) * D],
                              p_fin[N * D + n * CPF:N * D + (n + 1) * CPF],
                              warm)
        return self.metrics()

    def metrics(self) -> Dict[str, np.ndarray]:
        F = self.F
        one = self.one
        out = {k: [] for k in METRICS}
        for nd in self.nodes:
            out["ipc"].append(F(nd.instr / max(nd.cycles, one)))
            out["fam_latency"].append(F(nd.fam_lat_sum / max(nd.fam_cnt, one)))
            out["demand_hit_fraction"].append(
                F(nd.demand_hit / max(nd.demand_fam, one)))
            out["corepf_hit_fraction"].append(
                F(nd.corepf_hit / max(nd.corepf_fam, one)))
            out["prefetches_issued"].append(nd.pf_issued)
            out["issue_rate"].append(nd.issue_rate)
            out["cache_occupancy"].append(
                F(F(nd.occupancy_count()) / F(nd.sets * nd.ways)))
        return {k: np.asarray(v, np.float64) for k, v in out.items()}


def simulate(s: dict, dtype=np.float32) -> Dict[str, np.ndarray]:
    """Reference metrics of one system dict (see ``grid.systems``)."""
    T = s["T"]
    pairs = [node_trace(w, node_seed(s["seed"], i), T)
             for i, w in enumerate(s["workloads"])]
    addrs = np.stack([a for a, _ in pairs])
    gaps = np.stack([g for _, g in pairs])
    return System(s, dtype).run(addrs, gaps, int(T * s["warmup_frac"]))
