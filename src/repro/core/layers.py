"""FatPaths layered routing (paper §5.2–§5.4).

A *layer* is a subset of links with its own shortest-path forwarding
function sigma_i.  Layer 0 always contains every link (minimal paths);
layers 1..n-1 are rho-sparsified and oriented into DAGs by random vertex
permutations (Listing 1), so their "shortest paths" are non-minimal paths
of the full network — the "fat" path diversity.

Construction schemes (§5.3):
  * ``rand``    — Listing 1 verbatim: keep directed edge (u, v) with
                  pi(u) < pi(v) and probability rho.
  * ``pi_min``  — overlap-minimising variant (§5.3.2): edge inclusion
                  probability is biased *against* edges already heavily used
                  by the shortest paths of previously built layers.
  * ``undir``   — ablation: sparsify without DAG orientation (layer graphs
                  stay undirected; forwarding remains loop-free because it
                  follows intra-layer shortest paths).
  * ``spain``   — SPAIN adaptation: each layer is a BFS spanning tree from a
                  random root (tree paths, resilience-style multipathing).
  * ``past``    — PAST adaptation: per-layer re-randomised shortest-path
                  trees on the full graph (one address-tree per layer).
  * ``ksp``     — k-shortest-paths adaptation: per-layer randomly perturbed
                  edge weights spread traffic over near-minimal paths.

Forwarding is destination-based: ``nh[i, s, t]`` = next hop at router s for
a packet tagged layer i, destination t.  Unreachable (layer, s, t) entries
are -1; the load balancer (transport sim) only assigns flowlets to layers
whose reach mask is set, and falls back to layer 0 otherwise (§C.3).

Table construction is BATCHED: whatever the scheme, every layer's APSP +
forwarding tables come out of ONE jitted device program built on the
semiring engine (:mod:`repro.core.paths`, :mod:`repro.kernels.semiring`).
The host only samples layer adjacencies (cheap, O(E) per layer) — and for
``pi_min``/``ksp`` even that runs on device, because their sampling is
coupled to previously built tables (usage bias) or to perturbed-weight
(min, +) distances.  Tie-breaks use per-stack PRNG keys; the choice among
equal-cost next hops is uniform, distribution-identical to the historical
host-side ``rng.random`` scoring.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from . import paths as paths_mod
from .topology import Topology

__all__ = ["LayeredRouting", "LoopCheckReport", "build_layers",
           "layer_disjoint_paths", "layer_disjoint_paths_batch"]

_UNREACH = 10_000


@dataclasses.dataclass(frozen=True)
class LoopCheckReport:
    """Outcome of :meth:`LayeredRouting.validate_loop_free`.

    Truthy iff every checked entry delivered.  ``witnesses`` holds the
    offending ``(layer, src, dst)`` triples (capped), each tagged in
    ``kinds`` as ``"hole"`` (walk fell off the table) or ``"loop"``
    (walk never reached dst within the hop budget).
    """

    ok: bool
    n_checked: int
    exhaustive: bool
    witnesses: Tuple[Tuple[int, int, int], ...] = ()
    kinds: Tuple[str, ...] = ()

    def __bool__(self) -> bool:
        return self.ok

    def describe(self) -> str:
        if self.ok:
            mode = "exhaustive" if self.exhaustive else "sampled"
            return f"loop-free ({self.n_checked} entries, {mode})"
        shown = ", ".join(f"{k}@(l={li},s={s},t={t})" for (li, s, t), k
                          in zip(self.witnesses, self.kinds))
        return (f"{len(self.witnesses)} bad forwarding entr"
                f"{'y' if len(self.witnesses) == 1 else 'ies'} "
                f"of {self.n_checked} checked: {shown}")


@dataclasses.dataclass
class LayeredRouting:
    """Stacked forwarding state for n layers over one topology."""

    topo: Topology
    scheme: str
    rho: float
    nh: np.ndarray          # (L, N, N) int32 next hop, -1 unreachable
    reach: np.ndarray       # (L, N, N) bool
    pathlen: np.ndarray     # (L, N, N) int16 intra-layer shortest-path length
    layer_adj: np.ndarray   # (L, N, N) bool directed layer adjacency
    build_stats: Optional[Dict[str, float]] = None  # wall-time split
    # Per-directed-link death step for mid-run failures ((N, N) int32,
    # INT32_MAX = never dies); None = pristine fabric.  Set by the
    # fault-injection engine (repro.core.failures.link_down_schedule).
    link_down_step: Optional[np.ndarray] = None
    # Mid-run churn schedule: per-directed-link sorted (down, up) step
    # intervals ((N, N, K, 2) int32, INT32_MAX = never; see
    # repro.core.failures.churn_schedule).  Capacity restores at up;
    # flowlets may re-pick the link only at up + churn_conv steps
    # (control-plane re-convergence delay).  None = no churn.
    link_churn: Optional[np.ndarray] = None
    churn_conv: int = 0
    # Compressed per-router (dst-block, next-hop set) tables — attached
    # when the stack was built with representation="compressed" (the
    # blocked engine's default).  Exactly reconstructs ``nh``; the
    # transport walk and the batched disjoint-path walk prefer it.
    compressed: Optional[paths_mod.CompressedTables] = None

    @property
    def n_layers(self) -> int:
        return int(self.nh.shape[0])

    def usable_layers(self, s: int, t: int) -> np.ndarray:
        return np.nonzero(self.reach[:, s, t])[0]

    def validate_loop_free(self, n_samples: int = 200, seed: int = 0,
                           max_hops: int = 64, raise_on_fail: bool = True,
                           max_witnesses: int = 16) -> LoopCheckReport:
        """Walk the tables for (layer, s, t) entries; every reachable
        entry must hit t within max_hops (shortest-path forwarding =>
        loop-free).  All samples walk in ONE batched table walk.

        When ``n_samples`` covers the whole ``L * N * (N - 1)`` entry
        space the check enumerates EVERY entry instead of sampling with
        replacement (sampling could silently miss entries while
        appearing thorough).  Returns a :class:`LoopCheckReport` naming
        the offending ``(layer, src, dst)`` witnesses (capped at
        ``max_witnesses``); with ``raise_on_fail`` (the default) a bad
        table raises ``AssertionError`` carrying the same witnesses.
        """
        L, N, _ = self.nh.shape
        total = L * N * (N - 1)
        exhaustive = n_samples >= total
        if exhaustive:
            li, s, t = np.nonzero(~np.eye(N, dtype=bool)[None]
                                  & np.ones((L, N, N), dtype=bool))
        else:
            rng = np.random.default_rng(seed)
            li = rng.integers(L, size=n_samples)
            s = rng.integers(N, size=n_samples)
            t = (s + 1 + rng.integers(N - 1, size=n_samples)) % N  # t != s
        keep = self.reach[li, s, t]
        li, s, t = li[keep], s[keep], t[keep]
        if len(li) == 0:
            return LoopCheckReport(ok=True, n_checked=0,
                                   exhaustive=exhaustive)
        seqs = paths_mod.walk_paths_layers(self.nh, li, s, t, max_hops)
        holes = (seqs < 0).any(axis=1)
        stuck = ~holes & (seqs[:, -1] != t)
        bad = holes | stuck
        witnesses = []
        kinds = []
        for i in np.nonzero(bad)[0][:max_witnesses]:
            witnesses.append((int(li[i]), int(s[i]), int(t[i])))
            kinds.append("hole" if holes[i] else "loop")
        report = LoopCheckReport(ok=not bad.any(), n_checked=int(len(li)),
                                 exhaustive=exhaustive,
                                 witnesses=tuple(witnesses),
                                 kinds=tuple(kinds))
        if raise_on_fail:
            assert report.ok, report.describe()
        return report


def _rand_layer(adj: np.ndarray, rho: float, rng: np.random.Generator,
                oriented: bool = True) -> np.ndarray:
    """One Listing-1 layer: directed DAG (or undirected if not oriented)."""
    n = adj.shape[0]
    pi = rng.permutation(n)
    iu, ju = np.nonzero(np.triu(adj, 1))
    keep = rng.random(len(iu)) < rho
    out = np.zeros((n, n), dtype=bool)
    u, v = iu[keep], ju[keep]
    if oriented:
        fwd = pi[u] < pi[v]
        uu = np.where(fwd, u, v)
        vv = np.where(fwd, v, u)
        out[uu, vv] = True
    else:
        out[u, v] = True
        out[v, u] = True
    return out


# -----------------------------------------------------------------------------
# Single-program builders for the schemes whose sampling depends on
# previously built tables (pi_min) or on weighted semiring distances (ksp).
# -----------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_layers", "max_l", "engine"))
def _pi_min_program(adj, nbr, iu, ju, key, n_layers, rho, max_l,
                    engine="dense"):
    """The whole §5.3.2 build as one device program: a scan over layers
    that samples each DAG biased against accumulated edge usage, builds
    its tables, and folds the counting-semiring usage fixpoint back into
    the next layer's sampling."""
    n = adj.shape[0]
    e = iu.shape[0]
    k0, krest = jax.random.split(key)
    nh0, reach0, dist0 = paths_mod._layer_tables_core(adj[None], nbr, k0,
                                                      max_l, engine)
    usage0 = paths_mod._edge_usage_core(nh0[0], reach0[0], max_l)

    def step(usage, k):
        k_pi, k_keep, k_fw = jax.random.split(k, 3)
        u_sym = usage + usage.T
        mx = u_sym.max()
        norm = jnp.where(mx > 0, u_sym / jnp.maximum(mx, 1e-30), 0.0)
        pi = jax.random.permutation(k_pi, n)
        # Edge keep-probability shrinks with historical usage but keeps
        # expected density ~= rho.
        raw = 1.0 - 0.75 * norm[iu, ju]
        prob = raw * (rho * e / jnp.maximum(raw.sum(), 1e-9))
        keep = jax.random.uniform(k_keep, (e,)) < jnp.clip(prob, 0.0, 1.0)
        fwd = pi[iu] < pi[ju]
        uu = jnp.where(fwd, iu, ju)
        vv = jnp.where(fwd, ju, iu)
        la = jnp.zeros((n, n), dtype=bool).at[uu, vv].set(keep)
        nh, reach, dist = paths_mod._layer_tables_core(la[None], nbr, k_fw,
                                                       max_l, engine)
        usage = usage + paths_mod._edge_usage_core(nh[0], reach[0], max_l)
        return usage, (la, nh[0], reach[0], dist[0])

    if n_layers > 1:
        keys = jax.random.split(krest, n_layers - 1)
        _, (las, nhs, reaches, dists) = jax.lax.scan(step, usage0, keys)
        la_all = jnp.concatenate([adj[None], las])
        nh_all = jnp.concatenate([nh0, nhs])
        reach_all = jnp.concatenate([reach0, reaches])
        dist_all = jnp.concatenate([dist0, dists])
    else:
        la_all, nh_all, reach_all, dist_all = adj[None], nh0, reach0, dist0
    return la_all, nh_all, reach_all, dist_all


@functools.partial(jax.jit, static_argnames=("n_layers", "max_l", "engine"))
def _ksp_program(adj, nbr, key, n_layers, max_l, engine="dense"):
    """k-shortest-paths-style layers in one program: per-layer perturbed
    edge weights, (min, +) semiring all-pairs distances, and next hops
    minimising ``w[s, u] + D[u, t]`` over neighbors u."""
    n = adj.shape[0]
    idx = jnp.arange(n)
    k0, kw = jax.random.split(key)
    nh0, reach0, dist0 = paths_mod._layer_tables_core(adj[None], nbr, k0,
                                                      max_l, engine)
    hop = dist0[0]
    kk = n_layers - 1
    u01 = jax.random.uniform(kw, (kk, n, n))
    w = jnp.where(adj[None], 1.0 + 0.25 * u01, jnp.inf)
    w = jnp.minimum(w, jnp.transpose(w, (0, 2, 1)))
    w = w.at[:, idx, idx].set(0.0)
    d = paths_mod._minplus_apsp_core(w, max_l)

    has_edge = jnp.take_along_axis(adj, nbr, axis=1)          # (N, D)
    rows = idx[:, None]

    def one_layer(args):
        w_l, d_l = args
        w_nbr = jnp.take_along_axis(w_l, nbr, axis=1)         # (N, D)
        cost = jnp.where(has_edge[:, :, None],
                         w_nbr[:, :, None] + d_l[nbr], jnp.inf)
        j = jnp.argmin(cost, axis=1)                          # (N, N)
        best = nbr[rows, j].astype(jnp.int32)
        nh = jnp.where(jnp.isfinite(cost.min(axis=1)), best, -1)
        return nh.at[idx, idx].set(idx)

    def one_layer_blocked(args):
        # Destination-chunked twin of one_layer: the (N, D, N) cost cube
        # becomes (N, D, _CHUNK) slabs; per-column argmin is identical.
        w_l, d_l = args
        ch = paths_mod._CHUNK
        nc = -(-n // ch)
        npad = nc * ch
        w_nbr = jnp.take_along_axis(w_l, nbr, axis=1)         # (N, D)
        d_p = jnp.full((n, npad), jnp.inf).at[:, :n].set(d_l)
        d_cs = jnp.moveaxis(d_p.reshape(n, nc, ch), 1, 0)     # (nc, N, C)

        def one_chunk(d_c):
            cost = jnp.where(has_edge[:, :, None],
                             w_nbr[:, :, None] + d_c[nbr], jnp.inf)
            j = jnp.argmin(cost, axis=1)                      # (N, C)
            best = nbr[rows, j].astype(jnp.int32)
            return jnp.where(jnp.isfinite(cost.min(axis=1)), best, -1)

        out = jax.lax.map(one_chunk, d_cs)                    # (nc, N, C)
        nh = jnp.moveaxis(out, 0, 1).reshape(n, npad)[:, :n]
        return nh.at[idx, idx].set(idx)

    layer_fn = one_layer_blocked if engine == "blocked" else one_layer
    nh_extra = jax.lax.map(layer_fn, (w, d))
    nh_all = jnp.concatenate([nh0, nh_extra])
    reach_all = jnp.broadcast_to((hop <= max_l)[None], (n_layers, n, n))
    dist_all = jnp.broadcast_to(hop[None], (n_layers, n, n))
    la_all = jnp.broadcast_to(adj[None], (n_layers, n, n))
    return la_all, nh_all, reach_all, dist_all


def build_layers(topo: Topology, n_layers: int, rho: float,
                 scheme: str = "rand", seed: int = 0,
                 max_len: Optional[int] = None,
                 engine: Optional[str] = None,
                 representation: Optional[str] = None) -> LayeredRouting:
    """Construct the FatPaths layer stack (layer 0 = all links, minimal).

    All L layers' tables come from ONE batched device program; there is
    no per-layer host loop for table construction.  ``build_stats`` on
    the result splits the build's wall time (``total_s``) into the device
    table program, dispatch to ``block_until_ready`` (``device_s``, the
    ``stack.device`` span), and the host rest (``host_s``: adjacency
    sampling, table conversion, compression in ``stack.compress``).

    ``engine`` overrides the ``REPRO_PATH_ENGINE`` resolution (``dense``
    below 512 routers, ``blocked`` — frontier APSP + chunked forwarding
    — above; both bit-identical).  ``representation`` picks the table
    form: ``"compressed"`` attaches :class:`~repro.core.paths
    .CompressedTables` to the result (the default whenever the engine
    resolves blocked), ``"dense"`` keeps plain arrays only.
    """
    adj = np.asarray(topo.adj, dtype=bool)
    n = adj.shape[0]
    eng = paths_mod.path_engine(n, engine)
    if representation in (None, "", "auto"):
        rep = "compressed" if eng == "blocked" else "dense"
    elif representation in ("dense", "compressed"):
        rep = representation
    else:
        raise ValueError(f"unknown representation {representation!r}")
    if max_len is None:
        # Allow "almost minimal" detours: nominal diameter + slack.
        max_len = max(6, topo.diameter_nominal + 4)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    nbr = jnp.asarray(paths_mod.neighbor_table(adj))
    adj_j = jnp.asarray(adj)

    with tracing.record() as rec:
        if scheme == "pi_min":
            iu, ju = np.nonzero(np.triu(adj, 1))
            program = functools.partial(
                _pi_min_program, adj_j, nbr, jnp.asarray(iu), jnp.asarray(ju),
                key, n_layers, float(rho), max_len, eng)
        elif scheme == "ksp":
            program = functools.partial(_ksp_program, adj_j, nbr, key,
                                        n_layers, max_len, eng)
        else:
            layer_adjs: List[np.ndarray] = [adj.copy()]
            if scheme in ("rand", "undir"):
                for _ in range(n_layers - 1):
                    layer_adjs.append(_rand_layer(
                        adj, rho, rng, oriented=(scheme == "rand")))
            elif scheme == "spain":
                for _ in range(n_layers - 1):
                    root = int(rng.integers(n))
                    layer_adjs.append(_bfs_tree(adj, root, rng))
            elif scheme == "past":
                for _ in range(n_layers - 1):
                    layer_adjs.append(adj.copy())  # re-randomised tie-breaks
            else:
                raise ValueError(f"unknown scheme {scheme!r}")
            la = jnp.asarray(np.stack(layer_adjs))

            def program():
                return (la, *paths_mod._layer_tables_program(
                    la, nbr, key, max_len, eng))
        with tracing.span("stack.device"):
            la, nh, reach, dist = program()
            jax.block_until_ready(nh)

        reach_np = np.asarray(reach)
        pathlen = np.where(reach_np, np.asarray(dist),
                           _UNREACH).astype(np.int16)
        nh_np = np.asarray(nh)
        compressed = None
        if rep == "compressed":
            with tracing.span("stack.compress"):
                compressed = paths_mod.CompressedTables.from_dense(nh_np)
    device_s = rec.seconds("stack.device")
    return LayeredRouting(
        topo=topo, scheme=scheme, rho=rho,
        nh=nh_np, reach=reach_np,
        pathlen=pathlen, layer_adj=np.asarray(la),
        build_stats={"total_s": rec.wall_s, "device_s": device_s,
                     "host_s": rec.wall_s - device_s},
        compressed=compressed,
    )


def _bfs_tree(adj: np.ndarray, root: int, rng: np.random.Generator) -> np.ndarray:
    """Random-order BFS spanning tree (undirected layer)."""
    n = adj.shape[0]
    tree = np.zeros((n, n), dtype=bool)
    seen = np.zeros(n, dtype=bool)
    seen[root] = True
    frontier = [root]
    while frontier:
        nxt: List[int] = []
        order = rng.permutation(len(frontier))
        for fi in order:
            v = frontier[fi]
            nbrs = np.nonzero(adj[v] & ~seen)[0]
            rng.shuffle(nbrs)
            for u in nbrs:
                if not seen[u]:
                    seen[u] = True
                    tree[v, u] = tree[u, v] = True
                    nxt.append(int(u))
        frontier = nxt
    return tree


def _greedy_disjoint(paths: np.ndarray, reach_lt: np.ndarray, t: int) -> int:
    """Greedy edge-disjoint count over one (L, max_hops+1) path batch."""
    kept_edges = set()
    count = 0
    for i in range(paths.shape[0]):
        if not reach_lt[i]:
            continue
        path = paths[i]
        edges = set()
        ok = True
        reached = False
        prev = int(path[0])
        for v in path[1:]:
            v = int(v)
            if prev == t:
                reached = True
                break
            if v < 0:
                ok = False
                break
            e = (min(prev, v), max(prev, v))
            if e in kept_edges or e in edges:
                ok = False
                break
            edges.add(e)
            prev = v
        if prev == t:
            reached = True
        if ok and reached and edges:
            kept_edges |= edges
            count += 1
    return count


def layer_disjoint_paths_batch(lr: LayeredRouting, s: np.ndarray,
                               t: np.ndarray, max_hops: int = 16
                               ) -> np.ndarray:
    """:func:`layer_disjoint_paths` for many (s, t) pairs: ALL
    (pair, layer) table walks happen in one batched call; only the cheap
    greedy edge-disjointness filter stays per pair.  When the routing
    carries compressed tables the walk gathers off them directly — the
    per-hop working set is O(pairs * L), never a dense (L, N, N) slice,
    which is what keeps this usable at paper scale."""
    s = np.asarray(s, dtype=np.int32)
    t = np.asarray(t, dtype=np.int32)
    n_pairs = len(s)
    L = lr.n_layers
    li = np.tile(np.arange(L, dtype=np.int32), n_pairs)
    ss = np.repeat(s, L)
    tt = np.repeat(t, L)
    tables = lr.compressed if lr.compressed is not None else lr.nh
    walks = paths_mod.walk_paths_layers(tables, li, ss, tt, max_hops)
    walks = walks.reshape(n_pairs, L, max_hops + 1)
    out = np.zeros(n_pairs, dtype=np.int64)
    for p in range(n_pairs):
        out[p] = _greedy_disjoint(walks[p], lr.reach[:, s[p], t[p]],
                                  int(t[p]))
    return out


def layer_disjoint_paths(lr: LayeredRouting, s: int, t: int,
                         max_hops: int = 16) -> int:
    """How many pairwise edge-disjoint (s->t) paths do the layers realise?

    Greedy: walk each usable layer's path, keep it if it shares no
    (undirected) edge with already-kept paths.  This is the quantity behind
    the paper's "nine layers suffice for three disjoint paths" (Fig 12).
    """
    return int(layer_disjoint_paths_batch(lr, np.array([s]), np.array([t]),
                                          max_hops)[0])
