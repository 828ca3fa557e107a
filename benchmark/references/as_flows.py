"""Plain reference of the BRITE AS deployment (`kind: as_flows`, `reference: as_flows`).

Float64 numpy: the topology the stock script builds, shortest paths on it, each flow's
path, and the fluid fixed point of the sparse CBR load over those paths, replica by
replica.  It imports nothing of `tpudes` and takes nothing the program made: the graph
is generated again from the configuration's generator settings, the flow endpoints from
its MRG32k3a stream, and each replica's offered rates from its own key, by the
Threefry-2x32 counter generator that keys `jax.random`, written out here.

What the stock script builds (`examples/brite-as.py`), restated:
  - a Barabasi-Albert graph of `n_nodes`, `m` links a new node, from an `(m + 1)`-clique,
    targets drawn by the repeated-endpoint trick (a uniform draw from the list of every
    link's two endpoints), from numpy's generator seeded by `SeedSequence((RngSeed,
    RngRun, seed))`; node positions uniform on the square plane, a link's delay its
    length at `propagation_m_per_s`, its rate uniform in `[bw_min_bps, bw_max_bps]`,
    drawn in that order from the same generator; a point-to-point link holds its rate in
    whole bit/s and its delay in whole ns;
  - `n_flows` CBR flows, source and destination drawn in turn from the MRG32k3a stream
    `(flow_stream_seed, 0, 0)` as `RandInt(0, n - 1)` (a destination equal to its source
    drawn again), `flow_kbps` in packets of `pkt_bytes`;
  - links and flows listed as the lowering lists them: node by node, each node's links
    in the order they were built, a link at its lower-numbered end (its two directions
    are directed links `e` and `E + e`), a flow at its source, a node's in the order
    drawn.

Departures from upstream ns-3 (BriteTopologyHelper, Ipv4GlobalRoutingHelper, UdpClient):
  D1 routes are shortest by hop count (every interface's metric is 1, as upstream's
     default), computed to a fixed point from each destination; where two neighbours lie
     on shortest paths the next hop is the one over the lowest-numbered directed link.
     Upstream's GlobalRouteManager runs Dijkstra from each router and breaks equal-cost
     ties its own way (the first candidate its SPF tree holds); on a Barabasi-Albert
     graph at m = 2 equal-cost paths are the rule, so the tie picks the path, and with it
     the delay and the load.  The engine's rule is this one.
  D2 a flow's outcome is fluid, not packets: a link delivers min(1, rate / load) of what
     reaches it, compounding along the path, and a link's load is the rate of each flow
     through it that survived the links before.  `fp_rounds` rounds of that relaxation,
     from every link delivering all, and not the fixed point itself; the last round's
     delivered fraction is computed with the previous round's link fractions.
  D3 delay: per link an M/M/1 queue, rho / (1 - rho) packet times with rho = min(util,
     0.99), plus one packet's serialization and the propagation delay, summed along the
     path; no start-up transient, no jitter.
  D4 a replica's offered rate of flow f is `flow_kbps` x exp(rate_jitter z - rate_jitter^2
     / 2), z standard normal, `normal(fold_in(launch key, replica), (n_flows,))` in
     float32: the program's Monte-Carlo axis, which upstream has not.
  D5 a path longer than `max_hops` links is unreachable.

Controls and faults, never part of a benchmark run: `precision="bfloat16"` holds the
link loads in bfloat16 between hops of the walk (a half-width load array: the
control); `metric="delay"` routes on propagation delay in place of hops; `rounds=3`
stops the relaxation a round early (it tells only where links are overfilled: a
configuration with a higher `flow_kbps`).
"""

from __future__ import annotations

import functools
import json

import numpy as np

# MRG32k3a (L'Ecuyer 1999), the stream ns-3's RngStream runs
_M1, _M2 = 4294967087, 4294944443
_A12, _A13N, _A21, _A23N = 1403580, 810728, 527612, 1370589


def _mrg32k3a(seed: int):
    """`RandU01` of the stream `(seed, 0, 0)`: the scalar seed expanded to all six
    state words, as ns-3 does."""
    s = seed % _M1 or 12345
    s1, s2 = [s] * 3, [s % _M2 or 12345] * 3
    while True:
        p1 = (_A12 * s1[1] - _A13N * s1[0]) % _M1
        s1 = [s1[1], s1[2], p1]
        p2 = (_A21 * s2[2] - _A23N * s2[0]) % _M2
        s2 = [s2[1], s2[2], p2]
        d = p1 - p2
        yield (d + _M1 if d <= 0 else d) / (_M1 + 1)


def _barabasi_albert(n: int, m: int, rng) -> np.ndarray:
    edges = [(i, j) for i in range(m + 1) for j in range(i + 1, m + 1)]
    endpoints = [x for e in edges for x in e]
    for v in range(m + 1, n):
        targets = []
        while len(targets) < m:
            picks = rng.integers(0, len(endpoints), size=2 * (m - len(targets)))
            for t in (endpoints[p] for p in picks):
                if len(targets) < m and t not in targets:
                    targets.append(t)
        edges += [(v, t) for t in targets]
        endpoints += [v] * m + targets
    return np.asarray(edges, np.int64)


@functools.cache
def _topology_of(text: str) -> dict:
    cfg = json.loads(text)
    topo, ph = cfg["topology"], cfg["physics"]
    n, m = int(topo["n_nodes"]), int(topo["m"])
    rng = np.random.default_rng(np.random.SeedSequence(
        entropy=(int(topo["rng_seed"]), int(topo["rng_run"]), int(topo["seed"]))))
    built = _barabasi_albert(n, m, rng)
    pos = rng.uniform(0.0, float(topo["plane_m"]), size=(n, 2))
    length = np.sqrt(((pos[built[:, 0]] - pos[built[:, 1]]) ** 2).sum(-1))
    delay_ns = (length / float(topo["propagation_m_per_s"]) * 1e9).astype(np.int64)
    rate = rng.uniform(float(topo["bw_min_bps"]), float(topo["bw_max_bps"]),
                       size=len(built)).astype(np.int64).astype(float)
    # the lowering's order: by lower end, then in the order the links were built
    low, high = built.min(axis=1), built.max(axis=1)
    order = np.lexsort((np.arange(len(built)), low))
    u01 = _mrg32k3a(int(topo["flow_stream_seed"]))
    src, dst = [], []
    for _ in range(int(topo["n_flows"])):
        s = int(next(u01) * n)
        d = int(next(u01) * n)
        while d == s:
            d = int(next(u01) * n)
        src.append(s)
        dst.append(d)
    interval_ns = round(ph["pkt_bytes"] * 8 / (ph["flow_kbps"] * 1e3) * 1e9)
    # the lowering lists flows node by node, a node's in the order installed
    by_source = np.argsort(src, kind="stable")
    return dict(
        n=n, edges=np.stack([low[order], high[order]], axis=1),
        delay_s=delay_ns[order] / 1e9, rate_bps=rate[order],
        src=np.asarray(src)[by_source], dst=np.asarray(dst)[by_source],
        flow_bps=np.full(len(src), 8.0 * ph["pkt_bytes"] / (interval_ns / 1e9)),
    )


def _text(cfg: dict) -> str:
    """What of a configuration the reference reads, as a key."""
    return json.dumps({k: cfg[k] for k in ("topology", "physics")}, sort_keys=True)


def topology(cfg: dict) -> dict:
    """The deployment's graph and flows, as the lowering lists them."""
    return _topology_of(_text(cfg))


def _by_tail(values, tail, n, reduce):
    """`reduce` of `values[:, k]` over the directed links k that leave each node
    (every node of a Barabasi-Albert graph has `m` links or more)."""
    order = np.argsort(tail, kind="stable")
    return reduce.reduceat(values[:, order], np.searchsorted(tail[order], np.arange(n)),
                           axis=1)


def routes(topo: dict, metric: str = "hops", max_hops: int = 32) -> dict:
    """Each flow's path as directed links (-1 past its end), its hop count and whether
    it arrives."""
    e = np.concatenate([topo["edges"], topo["edges"][:, ::-1]])
    tail, head = e[:, 0], e[:, 1]
    w = (np.ones(len(e)) if metric == "hops"
         else np.concatenate([topo["delay_s"], topo["delay_s"]]))
    n = topo["n"]
    dests, row = np.unique(topo["dst"], return_inverse=True)
    dist = np.full((len(dests), n), np.inf)
    dist[np.arange(len(dests)), dests] = 0.0
    while True:                       # relax every link until nothing moves
        relaxed = np.minimum(dist, _by_tail(dist[:, head] + w, tail, n, np.minimum))
        if np.array_equal(relaxed, dist):
            break
        dist = relaxed
    score = dist[:, head] + w
    best = _by_tail(score, tail, n, np.minimum)
    index = np.where(score == best[:, tail], np.arange(len(e)), len(e))
    next_link = _by_tail(index, tail, n, np.minimum)
    F = len(topo["src"])
    path = np.full((F, max_hops), -1)
    arrived = np.zeros(F, bool)
    for f in range(F):
        at = topo["src"][f]
        for h in range(max_hops):
            if at == topo["dst"][f]:
                break
            path[f, h] = next_link[row[f], at]
            at = head[path[f, h]]
        arrived[f] = at == topo["dst"][f]
    reached = arrived & np.isfinite(dist[row, topo["src"]])
    return dict(path=path, hops=(path >= 0).sum(axis=1), reached=reached)


#: Threefry-2x32's rotation schedule (Salmon et al., SC 2011), the generator jax keys
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _threefry(k1, k2, x1, x2):
    """Threefry-2x32, 20 rounds, of the counters `(x1, x2)` under the key `(k1, k2)`;
    uint32 arrays that broadcast."""
    k1, k2 = np.atleast_1d(np.uint32(k1)), np.atleast_1d(np.uint32(k2))
    ks = (k1, k2, k1 ^ k2 ^ np.uint32(0x1BD11BDA))
    x1 = np.asarray(x1, np.uint32) + ks[0]      # arrays wrap around in silence
    x2 = np.asarray(x2, np.uint32) + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x1 = x1 + x2
            x2 = x1 ^ ((x2 << np.uint32(r)) | (x2 >> np.uint32(32 - r)))
        x1 = x1 + ks[(i + 1) % 3]
        x2 = x2 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x1, x2


def _fold_in(key, data):
    """`fold_in(key, data)`: the key's words hash the counter pair `(0, data)`."""
    return _threefry(key[0], key[1], 0, data)


def launch_key(seed: int, index: int):
    """The key of launch `index` of a run with `--seed`, two uint32 words as the mc
    driver holds them (its rule: `fold_in(fold_in(PRNGKey(seed mod 2^31), seed >>
    31), index)`)."""
    key = (np.uint32(0), np.uint32(int(seed) & 0x7FFFFFFF))
    return np.concatenate(_fold_in(_fold_in(key, int(seed) >> 31), int(index)))


def draws(key, rows, n_flows: int) -> np.ndarray:
    """The standard normal draws of each replica r of `rows` under `fold_in(key, r)`:
    `n_flows` uniform floats in [-1, 1) from the key's 32-bit counter stream (23 bits
    of mantissa each, as jax's `uniform` in float32 makes them), then sqrt(2)
    erfinv(u) in float64 where jax takes float32."""
    from scipy.special import erfinv

    k1, k2 = _fold_in(key, np.asarray(rows, np.uint32))
    b1, b2 = _threefry(k1[:, None], k2[:, None], 0, np.arange(n_flows, dtype=np.uint32))
    bits = ((b1 ^ b2) >> np.uint32(9)) | np.float32(1.0).view(np.uint32)
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = np.maximum(lo, (bits.view(np.float32) - np.float32(1.0))
                   * (np.float32(1.0) - lo) + lo)
    return np.sqrt(2.0) * erfinv(u.astype(np.float64))


def _rounder(precision: str):
    if precision == "float64":
        return lambda x: x
    if precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    from ml_dtypes import bfloat16

    return lambda x: x.astype(np.float32).astype(bfloat16).astype(np.float64)


def fluid(cfg: dict, topo: dict, route: dict, z: np.ndarray,
          precision: str = "float64", rounds: int | None = None) -> dict:
    """The fluid fixed point for the replicas whose draws are the rows of `z`."""
    ph = cfg["physics"]
    q = _rounder(precision)
    rounds = int(ph["fp_rounds"]) if rounds is None else int(rounds)
    jitter, reached, path = float(ph["rate_jitter"]), route["reached"], route["path"]
    rate = topo["flow_bps"] * np.exp(jitter * z - 0.5 * jitter ** 2)
    rate = np.where(reached[None, :], rate, 0.0)
    links, local = np.unique(path[path >= 0], return_inverse=True)
    at = np.full(path.shape, -1)
    at[path >= 0] = local
    cap = np.concatenate([topo["rate_bps"], topo["rate_bps"]])[links]
    prop = np.concatenate([topo["delay_s"], topo["delay_s"]])[links]
    R, L = z.shape[0], len(links)
    hops = []                       # per hop: its flows, their links, grouped by link
    for h in range(path.shape[1]):
        flows = np.flatnonzero(at[:, h] >= 0)
        if flows.size:
            order = np.argsort(at[flows, h], kind="stable")
            cols, starts = np.unique(at[flows[order], h], return_index=True)
            hops.append((flows, at[flows, h], order, cols, starts))
    lfrac = np.zeros((R, L))
    for _ in range(rounds):
        lg, load = np.zeros(rate.shape), np.zeros((R, L))
        for flows, cols_f, order, cols, starts in hops:
            arriving = rate[:, flows] * np.exp(lg[:, flows])
            load[:, cols] = q(load[:, cols] + np.add.reduceat(
                arriving[:, order], starts, axis=1))
            lg[:, flows] += lfrac[:, cols_f]
        util = load / cap[None, :]
        lfrac = np.log(np.minimum(1.0, 1.0 / np.maximum(util, 1e-9)))
    frac = np.where(reached[None, :], np.exp(lg), 0.0)
    bits = 8.0 * ph["pkt_bytes"] / cap
    rho = np.minimum(util, 0.99)
    per_link = rho / (1.0 - rho) * bits + bits + prop
    delay = np.zeros(rate.shape)
    for flows, cols_f, _, _, _ in hops:
        delay[:, flows] += per_link[:, cols_f]
    return dict(
        goodput_bps=rate * frac, delivered_frac=frac,
        delay_s=np.where(reached[None, :], delay, np.inf),
        max_util=util.max(axis=1, initial=0.0),
    )


@functools.cache
def _routes_of(text: str, metric: str) -> dict:
    cfg = json.loads(text)
    return routes(topology(cfg), metric, int(cfg["physics"]["max_hops"]))


def routes_of(cfg: dict, metric: str | None = None) -> dict:
    """`routes` of the configuration's topology, on its metric unless told another."""
    return _routes_of(_text(cfg), metric or cfg["physics"]["spf_metric"])


def simulate(cfg: dict, horizon_s: float, replicas: int, seed: int,
             precision: str = "float64", metric: str | None = None,
             rounds: int | None = None, launch: int = 0) -> dict:
    """Replicas 0 .. `replicas` - 1 of launch `launch` of a run with `--seed`, as the
    program returns them (`horizon_s` moves nothing: the fixed point has none), and
    under `launch` the key they were drawn from."""
    topo, route = topology(cfg), routes_of(cfg, metric)
    z = draws(launch_key(seed, launch), np.arange(replicas), len(topo["src"]))
    out = fluid(cfg, topo, route, z, precision, rounds)
    return dict(out, hops=route["hops"], unreachable=~route["reached"],
                launch={"seed": int(seed), "index": int(launch)})


def sample(replicas: int, traffic: dict) -> np.ndarray:
    """The fixed replicas of a launch that the reference recomputes: the mix's
    `reference_replicas`, evenly spread, the first and the last among them."""
    n = min(replicas, int(traffic.get("reference_replicas", 64)))
    return np.unique(np.linspace(0, replicas - 1, n).round().astype(int))


def criterion(out: dict) -> str | None:
    """`brite-as.py`'s own exit criterion on the lifted result (the mean delivered
    fraction above one half): None where it holds, else what failed."""
    if not float(np.mean(np.asarray(out["delivered_frac"]))) > 0.5:
        return "mean delivered_frac > 0.5"
    return None


def kpi(out: dict) -> float:
    """Mean aggregate goodput, Mbit/s a replica."""
    return float(np.asarray(out["goodput_bps"], float).sum(axis=-1).mean() / 1e6)


def _worst(got, want, mask) -> float:
    """Largest relative error over the entries `mask` selects."""
    got, want = np.asarray(got, float), np.asarray(want, float)
    mask = np.broadcast_to(mask, got.shape)
    got, want = got[mask], want[mask]
    if not got.size:
        return 0.0
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-300)))


def compare(cfg: dict, traffic: dict, outs: list, expected_rows: int,
            seed: int) -> dict:
    """Numbers that decide `correct`: rows, the routes of every launch exactly, and a
    fixed sample of replicas of the first `reference_launches` launches, replica by
    replica, against the reference on the same draws.

    A launch's key is the driver's for its index; a traced run's window starts after
    its `trace_launches`, so both indices are tried and the one whose draws the result
    holds is kept (the other reads gaps of order one).  A result that `simulate` made
    names its own key."""
    topo, route = topology(cfg), routes_of(cfg)
    F = len(topo["src"])

    def whole(o):
        good = np.asarray(o.get("goodput_bps", ()))
        return (good.ndim == 2 and good.shape[1] == F
                and np.asarray(o.get("delay_s", ())).shape == good.shape
                and np.asarray(o.get("max_util", ())).shape == good.shape[:1]
                and np.asarray(o.get("hops", ())).shape == (F,)
                and np.asarray(o.get("unreachable", ())).shape == (F,))

    done = [(i, o) for i, o in enumerate(outs) if whole(o)]
    rows = sum(int(np.asarray(o["goodput_bps"]).shape[0]) for _, o in done)
    numbers = {"rows_missing": float(expected_rows - rows)}
    if not done:
        return numbers
    numbers["hops_differ"] = float(max(
        np.sum(np.asarray(o["hops"]) != route["hops"]) for _, o in done))
    numbers["unreachable_differ"] = float(max(
        np.sum(np.asarray(o["unreachable"], bool) != ~route["reached"])
        for _, o in done))
    traced = int(traffic.get("trace_launches", 0))
    gaps = dict(goodput_gap=0.0, delay_gap=0.0, max_util_gap=0.0)
    reached = route["reached"][None, :]
    for i, o in done[: int(traffic.get("reference_launches", 4))]:
        picked = sample(np.asarray(o["goodput_bps"]).shape[0], traffic)
        keys = ([(o["launch"]["seed"], o["launch"]["index"])] if "launch" in o
                else [(seed, i), (seed, i + traced)][: 2 if traced else 1])
        tried = []
        for key in keys:
            want = fluid(cfg, topo, route, draws(launch_key(*key), picked, F))
            tried.append(dict(
                goodput_gap=_worst(np.asarray(o["goodput_bps"])[picked],
                                   want["goodput_bps"], reached),
                delay_gap=_worst(np.asarray(o["delay_s"])[picked],
                                 want["delay_s"], reached),
                max_util_gap=_worst(np.asarray(o["max_util"])[picked],
                                    want["max_util"], True),
            ))
        best = min(tried, key=lambda g: g["goodput_gap"])
        gaps = {k: max(v, best[k]) for k, v in gaps.items()}
    numbers.update(gaps)
    return numbers
