"""Seeded deployment generation for the benchmark, in plain numpy.

A copy of the program's four paper scenarios (`repro.core.scenarios`), kept
here so that the yardstick does not move when the program's generators do.
Every instance is a frozen record of numpy arrays (`Instance`); `problem()`
turns one into the program's input type at the boundary, and
`reference.py` reads the record directly.

Differences from the originals, all on the input side:
  * the hop bound (unweighted diameter + 2) is computed here on the host
    with scipy, not by the program's device APSP, once per topology;
  * each fleet takes its application draw (sources, destinations, rates)
    from a seed of its own (the paper fixes one draw per topology).
"""
from __future__ import annotations

import dataclasses
import functools

import networkx as nx
import numpy as np
from scipy.sparse.csgraph import shortest_path

BIG = np.float32(1e18)

GEANT_EDGES = [
    (0, 1), (0, 2), (1, 3), (1, 6), (2, 3), (2, 4), (3, 5), (4, 5),
    (4, 7), (5, 8), (6, 8), (6, 9), (7, 8), (7, 11), (8, 10), (9, 10),
    (9, 12), (10, 13), (11, 14), (12, 13), (12, 15), (13, 16), (14, 17),
    (15, 16), (15, 18), (16, 19), (17, 18), (17, 20), (18, 21), (19, 21),
    (20, 21), (3, 10), (8, 13), (5, 16), (2, 9),
]


@dataclasses.dataclass(frozen=True)
class Instance:
    """One placement/routing instance (the program's `Problem`, in numpy).

    adj [V,V] {0,1}; mu [V,V] link rates (BIG off the edges); nu [V] compute
    rates; src/dst [A]; lam [A]; L [A,K] stage packet sizes; w [A,P]
    partition workloads; cost: (kind, rho_max, w_comm, w_comp).
    """

    adj: np.ndarray
    mu: np.ndarray
    nu: np.ndarray
    src: np.ndarray
    dst: np.ndarray
    lam: np.ndarray
    L: np.ndarray
    w: np.ndarray
    hop_bound: int
    cost: tuple = ("mm1", 0.95, 1.0, 1.0)

    @property
    def n_nodes(self) -> int:
        return int(self.adj.shape[0])

    @property
    def n_apps(self) -> int:
        return int(self.src.shape[0])


def hop_bound(adj: np.ndarray) -> int:
    """Unweighted diameter over reachable pairs, plus 2 (the program's
    `Problem.hop_bound` definition, core/structs.py)."""
    d = shortest_path(adj > 0, unweighted=True, directed=True)
    return int(np.max(np.where(np.isfinite(d), d, 0.0))) + 2


@functools.lru_cache(maxsize=None)
def topology(name):
    """(n, adj, mu, nu, n_apps, source pool, destination mode, hop bound)
    of one of the paper's topologies; built once per process."""
    n, und_edges, mu_map, nu, n_apps, pool, mode = TOPOLOGIES[name]()
    adj = np.zeros((n, n), np.float32)
    mu = np.full((n, n), 1.0, np.float32)
    for (u, v) in und_edges:
        for (i, j) in ((u, v), (v, u)):
            adj[i, j] = 1.0
            mu[i, j] = mu_map.get((i, j), mu_map.get((u, v), 10.0))
    mu = np.where(adj > 0, mu, BIG).astype(np.float32)
    return n, adj, mu, np.asarray(nu, np.float32), n_apps, pool, mode, hop_bound(adj)


def gen_apps(rng, n_apps, src_pool, dst_mode, n_nodes, spec, load_scale):
    src = rng.choice(src_pool, size=n_apps)
    dst = src.copy() if dst_mode == "same" else rng.randint(0, n_nodes, size=n_apps)
    lam = rng.uniform(*spec["lam_range"], size=n_apps) * load_scale
    L = np.tile(np.asarray(spec["L"], np.float32), (n_apps, 1))
    w = np.tile(np.asarray(spec["w"], np.float32), (n_apps, 1))
    return (
        src.astype(np.int32), dst.astype(np.int32), lam.astype(np.float32),
        L, w,
    )


# -- the paper's four evaluation topologies (Table I) -----------------------

def _iot_topology():
    edges, mu_map = [], {}
    for e in [(1, 2), (2, 3), (3, 4), (4, 1)]:
        edges.append(e)
        mu_map[e] = 16.0
    for s in (1, 2, 3, 4):
        edges.append((s, 0))
        mu_map[(s, 0)] = 12.0
    for idx, dev in enumerate(range(5, 17)):
        for s in (1 + (idx % 4), 1 + ((idx + 1) % 4)):
            edges.append((dev, s))
            mu_map[(dev, s)] = 8.0
    nu = np.array([80.0] + [12.0] * 4 + [2.0] * 12, np.float32)
    return 17, edges, mu_map, nu, 20, np.arange(5, 17), "same"


def _mesh_topology():
    side, edges = 5, []
    for r in range(side):
        for c in range(side):
            u = r * side + c
            if c + 1 < side:
                edges.append((u, u + 1))
            if r + 1 < side:
                edges.append((u, u + side))
    n = side * side
    return n, edges, {}, np.full(n, 10.0, np.float32), 40, np.arange(n), "random"


def _smallworld_topology():
    g = nx.connected_watts_strogatz_graph(30, 4, 0.1, seed=7)
    return 30, list(g.edges()), {}, np.full(30, 10.0, np.float32), 40, np.arange(30), "random"


def _geant_topology():
    return 22, GEANT_EDGES, {}, np.full(22, 10.0, np.float32), 30, np.arange(22), "random"


TOPOLOGIES = {
    "iot": _iot_topology,
    "mesh": _mesh_topology,
    "smallworld": _smallworld_topology,
    "geant": _geant_topology,
}


def scenario(spec, name, seed, load):
    n, adj, mu, nu, n_apps, pool, mode, hb = topology(name)
    rng = np.random.RandomState(seed)
    src, dst, lam, L, w = gen_apps(rng, n_apps, pool, mode, n, spec, load)
    return Instance(adj=adj, mu=mu, nu=nu, src=src, dst=dst, lam=lam, L=L, w=w,
                    hop_bound=hb, cost=tuple(spec["cost"]))


def draw(spec, traffic, seed):
    """One fleet: every topology of the config at every load of the mix's
    `load_grid`, with one application draw per topology from `seed`, shared
    across its loads (the paper's Fig.-4 sweep)."""
    master = np.random.RandomState(seed)
    out = []
    for name in spec["topologies"]:
        sub = int(master.randint(0, 2**31 - 1))
        out.extend(scenario(spec, name, sub, float(f)) for f in traffic["load_grid"])
    return out


def problem(inst: Instance):
    """The program's input type for one instance (numpy leaves)."""
    from repro.core.structs import Apps, CostModel, Network, Problem

    kind, rho, wc, wp = inst.cost
    return Problem(
        net=Network(adj=inst.adj, mu=inst.mu, nu=inst.nu),
        apps=Apps(
            src=inst.src, dst=inst.dst, lam=inst.lam, L=inst.L, w=inst.w,
            parts=np.full(inst.n_apps, inst.w.shape[1], np.int32),
        ),
        cost=CostModel(kind=kind, rho_max=rho, w_comm=wc, w_comp=wp),
        hop_bound=inst.hop_bound,
    )
