"""Plain reference for the benchmark's cells.

It computes, from a configuration's ``reference`` parameters, a traffic
mix's ``reference`` parameters and a seed, what one cell of the simulator
must produce: the forwarding tables of every layer, each flow's
completion step and delivered bytes, and the evaluator's metrics.  It is
written from the semantics the simulator documents (FatPaths paper §5
and §7; the simulator's transport step), with numpy for the arithmetic
and ``jax.random`` only for the documented random streams: the per-entry
next-hop tie-break uniforms, the per-flow initial layer draw and the
per-(flow, chunk) flowlet uniforms.  It imports nothing of the program
under test and takes nothing it made.

Scope: static workloads (every flow starts at step 0), the ``ndp``
transport, no fault or churn lanes, no loss recovery.  That is what the
benchmark's cells run.

``wf_dtype`` selects the arithmetic of the water-filling step.  float32
is the configuration's precision; bfloat16 is the control that the
comparison has to reject (``chipbench/check.py``).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, Optional

import numpy as np

__all__ = ["Fabric", "slim_fly", "fabric", "permutation", "distances",
           "forwarding", "routing_tables", "flow_paths", "simulate",
           "evaluate", "run_cell"]


# ---------------------------------------------------------------------------
# Fabric: MMS Slim Fly (Besta & Hoefler, SC'14), router and edge labelling
# ---------------------------------------------------------------------------
class Fabric:
    """Routers, endpoints and directed-edge ids of one topology."""

    def __init__(self, adj: np.ndarray, conc: np.ndarray):
        self.adj = adj
        self.n = adj.shape[0]
        self.ep2r = np.repeat(np.arange(self.n), conc)
        u, v = np.nonzero(adj)                 # lexicographic edge ids
        self.eix = np.full((self.n, self.n), -1, np.int64)
        self.eix[u, v] = np.arange(len(u))
        self.n_edges = len(u)


def _primitive_root(q: int) -> int:
    factors = {f for f in range(2, q) if (q - 1) % f == 0
               and all(f % d for d in range(2, int(f ** 0.5) + 1))}
    for g in range(2, q):
        if all(pow(g, (q - 1) // f, q) != 1 for f in factors):
            return g
    raise ValueError(f"no primitive root modulo {q}")


def slim_fly(q: int):
    """(adjacency, endpoints per router) of the MMS graph over GF(q).

    Routers (0, x, y) are ``x*q + y`` and (1, m, c) are ``q*q + m*q + c``;
    (0,x,y)~(0,x,y') iff y-y' in X, (1,m,c)~(1,m,c') iff c-c' in X',
    (0,x,y)~(1,m,c) iff y = m*x + c, with X the generator set built from
    the smallest primitive root xi and X' = xi*X.  p = ceil(k'/2)."""
    delta = 1 if q % 4 == 1 else -1
    xi = _primitive_root(q)
    if delta == 1:
        gen = {pow(xi, 2 * i, q) for i in range((q - 1) // 2)}
    else:
        base = {pow(xi, 2 * i, q) for i in range((q + 1) // 4)}
        gen = base | {(q - b) % q for b in base}
    gen_p = {(xi * b) % q for b in gen}
    n = 2 * q * q
    adj = np.zeros((n, n), bool)
    for x in range(q):
        for y in range(q):
            for y2 in range(q):
                if (y - y2) % q in gen:
                    adj[x * q + y, x * q + y2] = True
                if (y - y2) % q in gen_p:
                    adj[q * q + x * q + y, q * q + x * q + y2] = True
    for x in range(q):
        for m in range(q):
            for c in range(q):
                adj[x * q + (m * x + c) % q, q * q + m * q + c] = True
    adj |= adj.T
    np.fill_diagonal(adj, False)
    kprime = (3 * q - delta) // 2
    return adj, np.full(n, (kprime + 1) // 2, np.int64)


def fabric(params: Dict) -> Fabric:
    if params["family"] != "sf":
        raise ValueError(f"the reference builds Slim Fly only, "
                         f"not {params['family']!r}")
    return Fabric(*slim_fly(int(params["q"])))


# ---------------------------------------------------------------------------
# Traffic: random permutation with the §3.4 randomised endpoint mapping
# ---------------------------------------------------------------------------
def permutation(n_endpoints: int, seed: int, randomize: bool = True):
    """(src, dst) endpoint ids: a derangement drawn from
    ``default_rng(seed)`` (redrawn until no endpoint talks to itself),
    relabelled through ``default_rng(seed + 101)`` when ``randomize``."""
    rng = np.random.default_rng(seed)
    while True:
        t = rng.permutation(n_endpoints)
        if not (t == np.arange(n_endpoints)).any():
            break
    if randomize:
        relabel = np.random.default_rng(seed + 101).permutation(n_endpoints)
        out = np.empty_like(t)
        out[relabel] = relabel[t]
        t = out
    src = np.arange(n_endpoints)
    keep = src != t
    return src[keep], t[keep]


# ---------------------------------------------------------------------------
# Forwarding tables
# ---------------------------------------------------------------------------
def distances(adj_l: np.ndarray, max_l: int) -> np.ndarray:
    """Directed BFS levels s -> t over one layer; pairs not reached
    within ``max_l`` hops get ``max_l + 1``."""
    n = adj_l.shape[0]
    dist = np.full((n, n), max_l + 1, np.int32)
    reach = adj_l | np.eye(n, dtype=bool)
    dist[adj_l] = 1
    np.fill_diagonal(dist, 0)
    a = adj_l.astype(np.float32)
    frontier = adj_l.copy()
    for level in range(2, max_l + 1):
        nxt = ((frontier.astype(np.float32) @ a) > 0) & ~reach
        if not nxt.any():
            break
        dist[nxt] = level
        reach |= nxt
        frontier = nxt
    return dist


def _uniforms(shape, seed: int) -> np.ndarray:
    import jax
    with _on_cpu(jax):
        return np.asarray(jax.random.uniform(jax.random.PRNGKey(seed), shape))


@contextlib.contextmanager
def _on_cpu(jax):
    """Keep the reference's random draws off the accelerator."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        cpu = None
    if cpu is None:
        yield
    else:
        with jax.default_device(cpu):
            yield


def forwarding(adj_stack: np.ndarray, dist: np.ndarray, base_adj: np.ndarray,
               seed: int) -> np.ndarray:
    """(L, N, N) next hops.  For each (layer, s, t) the candidates are
    the neighbours u of s in the base graph, in ascending id, with the
    edge s->u in the layer and ``dist[u, t] + 1 == dist[s, t]``; the
    r-th candidate is taken with ``r = min(floor(u01 * count), count-1)``
    and u01 the (layer, s, t) entry of ``uniform(PRNGKey(seed), (L, N,
    N))``.  No candidate gives -1; the diagonal is s itself."""
    n_layers, n, _ = adj_stack.shape
    u01 = _uniforms((n_layers, n, n), seed)
    deg = base_adj.sum(axis=1)
    dmax = int(deg.max())
    nbr = np.argsort(~base_adj, axis=1, kind="stable")[:, :dmax]
    rows = np.arange(n)[:, None]
    nh = np.empty((n_layers, n, n), np.int32)
    for layer in range(n_layers):
        d = dist[layer]
        has_edge = adj_stack[layer][rows, nbr]                  # (N, D)
        ok = has_edge[:, :, None] & (d[nbr] + 1 == d[:, None, :])
        cnt = ok.sum(axis=1).astype(np.int32)                   # (N, N)
        r = (u01[layer] * cnt.astype(np.float32)).astype(np.int32)
        r = np.clip(r, 0, np.maximum(cnt - 1, 0))
        csum = np.cumsum(ok, axis=1, dtype=np.int32)
        j = np.argmax(ok & (csum == (r + 1)[:, None, :]), axis=1)
        nh[layer] = np.where(cnt > 0, nbr[rows, j], -1)
        np.fill_diagonal(nh[layer], np.arange(n))
    return nh


def routing_tables(fab: Fabric, params: Dict, seed: int):
    """(next hops (L,N,N), reach (L,N,N)) of the routing scheme.

    * ``minimal`` (ECMP/LetFlow substrate): ``n_tables`` copies of the
      full graph, each with its own tie-breaks; entries beyond
      ``max_len`` are -1.
    * ``layers`` (FatPaths ``rand``, Listing 1): layer 0 is the full
      graph; each further layer draws, from ``default_rng(seed)``, a
      vertex permutation pi and keeps each undirected link with
      probability rho, oriented from lower to higher pi."""
    adj = fab.adj
    max_l = int(params["max_len"])
    if params["tables"] == "minimal":
        layers = [adj] * int(params["n_tables"])
    elif params["tables"] == "layers":
        rng = np.random.default_rng(seed)
        iu, ju = np.nonzero(np.triu(adj, 1))
        layers = [adj]
        for _ in range(int(params["n_layers"]) - 1):
            pi = rng.permutation(fab.n)
            keep = rng.random(len(iu)) < float(params["rho"])
            u, v = iu[keep], ju[keep]
            fwd = pi[u] < pi[v]
            la = np.zeros_like(adj)
            la[np.where(fwd, u, v), np.where(fwd, v, u)] = True
            layers.append(la)
    else:
        raise ValueError(f"unknown table kind {params['tables']!r}")
    stack = np.stack(layers)
    if params["tables"] == "minimal":
        d0 = distances(adj, max_l)
        dist = np.broadcast_to(d0, stack.shape)
    else:
        dist = np.stack([distances(la, max_l) for la in layers])
    nh = forwarding(stack, dist, adj, seed)
    reach = dist <= max_l
    if params["tables"] == "minimal":
        nh[:, ~reach[0]] = -1
        for layer in range(len(layers)):
            np.fill_diagonal(nh[layer], np.arange(fab.n))
    return nh, reach


def flow_paths(fab: Fabric, nh: np.ndarray, src_r: np.ndarray,
               dst_r: np.ndarray, max_hops: int):
    """Walk every flow through every layer's table: (L, F, hmax) edge ids
    (-1 past the destination or at a hole) and the (L, F) routed mask;
    ``hmax`` is the longest walk, at least 1."""
    n_layers = nh.shape[0]
    edges = np.full((n_layers, len(src_r), max_hops), -1, np.int64)
    routed = np.zeros((n_layers, len(src_r)), bool)
    for layer in range(n_layers):
        cur = src_r.copy()
        for h in range(max_hops):
            nxt = nh[layer, cur, dst_r]
            stop = (cur == dst_r) | (nxt < 0)
            edges[layer, :, h] = np.where(stop, -1,
                                          fab.eix[cur, np.where(stop, cur,
                                                                nxt)])
            cur = np.where(stop, cur, nxt)
        routed[layer] = cur == dst_r
    hmax = max(1, int((edges >= 0).sum(axis=2).max()))
    return edges[:, :, :hmax], routed


# ---------------------------------------------------------------------------
# Transport: the flow-level scan (ndp, max-min water-filling, flowlets)
# ---------------------------------------------------------------------------
def _waterfill(edges, w, desired, e_tot: int, fair_iters: int, dtype):
    """Max-min water-filling over virtual links of capacity 1 (line
    units): fair share = 1 / claimants, demand = min(desired, share),
    then ``fair_iters`` rounds scaling each demand by its path's worst
    ``min(1, 1/load)``.  Link id ``e_tot - 1`` is the trash slot: it
    takes every unused hop slot and never enters a min."""
    live = edges < e_tot - 1
    flat = edges.ravel()
    one = dtype(1.0)

    def links(values):
        tot = np.bincount(flat, weights=np.repeat(values.astype(np.float64),
                                                  edges.shape[1]),
                          minlength=e_tot)
        return tot.astype(dtype)

    count = links(w)
    fair = one / np.maximum(count, dtype(1e-9))
    share = np.min(np.where(live, fair[edges], dtype(np.inf)), axis=1)
    d = np.minimum(desired.astype(dtype), share)
    for _ in range(fair_iters):
        load = links(d)
        scale = np.minimum(one, one / np.maximum(load, dtype(1e-9)))
        s = np.min(np.where(live, scale[edges], dtype(np.inf)), axis=1)
        d = d * np.where(np.isfinite(s), s, dtype(0.0))
    return d.astype(np.float32)


def _pick_layers(u, usable):
    """The r-th usable layer, r = min(floor(u * n_usable), n_usable-1);
    layer 0 when none is usable."""
    c = np.cumsum(usable.astype(np.int32), axis=1)
    n = c[:, -1]
    r = np.minimum((u * n.astype(np.float32)).astype(np.int32),
                   np.maximum(n - 1, 0))
    pick = np.argmax(c > r[:, None], axis=1).astype(np.int32)
    return np.where(n > 0, pick, 0)


@functools.lru_cache(maxsize=None)
def _draw_programs():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def initial(key, n_flows):
        k_init, k_scan = jax.random.split(key)
        idx = jnp.arange(n_flows)
        u0 = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(k_init, i), (2,)))(idx)
        return u0[:, 0], jax.vmap(lambda i: jax.random.fold_in(k_scan, i))(idx)

    @functools.partial(jax.jit, static_argnums=2)
    def chunk(flow_keys, c, length):
        return jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, c), (length, 2)))(flow_keys)

    return initial, chunk


def _flow_draws(sim_seed: int, n_flows: int):
    """The documented streams: ``k_init, k_scan = split(PRNGKey(seed))``;
    the initial layer uses ``uniform(fold_in(k_init, i), (2,))[0]``; the
    flowlet draws of chunk c are ``uniform(fold_in(fold_in(k_scan, i),
    c), (chunk, 2))`` for flow i.  Returns the initial draws and a
    function of (c, chunk) giving that chunk's (chunk, F, 2) draws."""
    import jax
    initial, chunk_fn = _draw_programs()
    with _on_cpu(jax):
        u0, flow_keys = initial(jax.random.PRNGKey(sim_seed), n_flows)
        u0 = np.asarray(u0)

    def chunk_draws(c: int, chunk: int) -> np.ndarray:
        with _on_cpu(jax):
            return np.moveaxis(np.asarray(chunk_fn(flow_keys, c, chunk)), 0, 1)

    return u0, chunk_draws


def simulate(path_edges: np.ndarray, routed: np.ndarray, path_hops,
             usable: np.ndarray, size: np.ndarray, e_tot: int, cfg: Dict,
             reroute: bool, sim_seed: int, wf_dtype=np.float32) -> Dict:
    """Per-flow end state of the transport scan.

    Every flow starts at step 0 at line rate (ndp).  Each step a flow
    that still has bytes and whose current layer routes it claims the
    links of its path; it sends its water-filled rate times line bytes
    per step, and finishes at the step its remaining bytes reach 0.
    Under flowlet balancing a still-active flow re-rolls its layer with
    probability ``dt/flowlet_gap * (1 - sent + gap_eps)``.  Steps run in
    chunks of ``chunk``; before each full chunk the run stops once every
    flow has finished or can never be routed."""
    f32 = np.float32
    n_layers, n_flows, _ = path_edges.shape
    chunk = int(cfg["chunk"])
    n_steps = int(cfg["steps"])
    line_bytes = f32(float(cfg["line_rate"]) * float(cfg["dt"]))
    p_scale = f32(float(cfg["dt"]) / float(cfg["flowlet_gap"]))
    gap_eps = f32(cfg["gap_eps"])
    rows = np.arange(n_flows)

    u_init, chunk_draws = _flow_draws(sim_seed, n_flows)
    layer = _pick_layers(u_init, usable)
    remaining = size.astype(f32).copy()
    hops = np.zeros(n_flows, f32)
    sent_acc = np.zeros(n_flows, f32)
    w_acc = np.zeros(n_flows, f32)
    depart = np.full(n_flows, -1, np.int32)
    if reroute:
        pickable = np.where(usable.any(axis=1, keepdims=True), usable,
                            (np.arange(n_layers) == 0)[None, :])
        pick_routable = np.any(pickable & routed.T, axis=1)
    else:
        pick_routable = np.zeros(n_flows, bool)

    def run_chunk(c: int, length: int):
        nonlocal remaining, hops, sent_acc, w_acc, depart, layer
        u = chunk_draws(c, chunk)[:length] if reroute else None
        for k in range(length):
            i = c * chunk + k
            done = remaining <= 0
            active = ~done
            edges = path_edges[layer, rows]
            send = active & routed[layer, rows]
            w = send.astype(f32)
            desired = w
            edges = np.where(send[:, None] & (edges >= 0), edges, e_tot - 1)
            sent = _waterfill(edges, w, desired, e_tot,
                              int(cfg["fair_iters"]), wf_dtype)
            new_remaining = np.maximum(remaining - (sent * line_bytes) * w,
                                       f32(0.0))
            newly = (new_remaining <= 0) & ~done
            hops = np.where(newly, path_hops[layer, rows], hops)
            depart = np.where(newly, np.int32(i), depart)
            if reroute:
                slack = f32(1.0) - np.clip(sent, f32(0.0), f32(1.0))
                p_gap = np.clip(p_scale * (slack + gap_eps), f32(0.0),
                                f32(1.0))
                roll = u[k, :, 0] < p_gap
                newpick = _pick_layers(u[k, :, 1], usable)
                layer = np.where(roll & active, newpick, layer)
            remaining = new_remaining
            sent_acc = sent_acc + sent
            w_acc = w_acc + w

    n_full, rem = divmod(n_steps, chunk)
    c = 0
    while c < n_full:
        stuck = ~routed[layer, rows] & ~pick_routable
        if np.all((remaining <= 0) | stuck):
            break
        run_chunk(c, chunk)
        c += 1
    if rem:
        run_chunk(n_full, rem)
    return dict(remaining=remaining, depart=depart, hops=hops,
                sent_acc=sent_acc, w_acc=w_acc, chunks=c)


def evaluate(size: np.ndarray, state: Dict, cfg: Dict) -> Dict[str, float]:
    """The transport evaluator's metrics of one simulation: FCT
    percentiles and mean (µs) over finished flows, finished share, mean
    per-flow throughput (GB/s) and the achieved share of demanded line
    rate.  FCT = (depart + 1) * dt + hops * link_latency + sw_latency."""
    f32 = np.float32
    size = size.astype(f32)
    dep = state["depart"]
    fct = ((dep.astype(f32) + f32(1.0)) * f32(cfg["dt"]) - f32(0.0)
           + state["hops"] * f32(cfg["link_latency"])
           + f32(cfg["sw_latency"]))
    fct = np.where(dep >= 0, fct, f32(np.nan))
    finished = state["remaining"] <= 0
    done = fct[finished]
    if len(done):
        p50 = float(np.quantile(done, 0.50) * 1e6)
        p99 = float(np.quantile(done, 0.99) * 1e6)
        mean = float(done.mean() * 1e6)
    else:
        p50 = p99 = mean = float("nan")
    tput = np.where(finished, size / np.maximum(fct, 1e-12), np.nan)
    tput_gbs = (float(np.nanmean(tput) / 1e9)
                if tput.size and not np.all(np.isnan(tput)) else float("nan"))
    sent = float(np.asarray(state["sent_acc"], np.float64).sum())
    want = float(np.asarray(state["w_acc"], np.float64).sum())
    return {"fct_p50_us": p50, "fct_p99_us": p99, "fct_mean_us": mean,
            "finished": float(finished.mean()), "tput_gbs": tput_gbs,
            "link_util": sent / max(want, 1.0)}


def run_cell(topology: Dict, routing: Dict, transport: Dict, traffic: Dict,
             seed: int, wf_dtype=np.float32,
             tables: Optional[np.ndarray] = None) -> Dict:
    """Everything one cell produces: ``nh`` (tables), per-flow
    ``depart``/``delivered``/``finished``, ``metrics`` and ``chunks``.
    ``tables`` reuses next hops this module computed before (the control
    runs the same cell twice and differs only in the scan)."""
    fab = fabric(topology)
    if traffic["kind"] != "permutation":
        raise ValueError(f"the reference draws permutations only, "
                         f"not {traffic['kind']!r}")
    src, dst = permutation(len(fab.ep2r), seed, bool(traffic["randomize"]))
    size = np.full(len(src), float(traffic["flow_size"]), np.float32)
    if tables is None:
        nh, reach = routing_tables(fab, routing, seed)
    else:
        nh, reach = tables
    src_r, dst_r = fab.ep2r[src], fab.ep2r[dst]
    edges, routed = flow_paths(fab, nh, src_r, dst_r,
                               int(transport["max_hops"]))
    n_layers, n_flows, _ = edges.shape
    n_ep = int(max(src.max(), dst.max()) + 1)
    e_tot = fab.n_edges + 2 * n_ep + 1
    path_edges = np.concatenate(
        [edges,
         np.broadcast_to((fab.n_edges + src)[None, :, None],
                         (n_layers, n_flows, 1)),
         np.broadcast_to((fab.n_edges + n_ep + dst)[None, :, None],
                         (n_layers, n_flows, 1))], axis=2)
    path_hops = (edges >= 0).sum(axis=2).astype(np.float32)
    usable = reach[:, src_r, dst_r].T
    if int(transport["sim_seeds"]) != 1:
        raise ValueError("the reference simulates one sim seed per cell")
    state = simulate(path_edges, routed, path_hops, usable, size, e_tot,
                     transport, routing["balancing"] != "ecmp", seed,
                     wf_dtype)
    out = {"nh": nh, "flow_size": float(traffic["flow_size"]),
           "hop_slots": path_edges.shape[2], "n_flows": n_flows,
           "depart": state["depart"], "delivered": size - state["remaining"],
           "finished": state["remaining"] <= 0, "chunks": state["chunks"],
           "metrics": evaluate(size, state, transport)}
    return out
