"""Plain reference for one placement/routing answer, in float64 numpy.

It imports nothing of the program. Given an instance (gen.Instance) and the
answer the program returned for it (partition hosts [A, P], forwarding
fractions phi [A, K, V', V'] over the program's padded envelope V' >= V,
and the reported objective J), it checks what the answer says against the
paper's model (arXiv 2604.25571, Eqs. 1-7 and 10-13):

  bad_hosts  partitions placed off a real node (an exact count);
  stray      forwarding mass a node sends anywhere but a neighbour, inside
             or outside the real block;
  mass_err   worst |sum_j phi_ij - m_i| over the nodes, where the emission
             mass m is 1 - x (the stage's next host absorbs it) or, on the
             final stage, 0 at the destination and 1 elsewhere (Eq. 2);
  J_gap      |J - J_ref| / J_ref, with J_ref the M/M/1 cost (quadratic
             continuation past rho_max) of the flows that phi and the hosts
             induce, each stage solved exactly by a dense LU (Eqs. 3-7).

Two numbers say how good the answer is, not only whether it is consistent:

  start_cost   the cost of the paper's zero-load start (section IV, method
               a). An optimiser that starts there and keeps its best
               iterate never ends above it, and on most instances ends far
               below: an answer that does not is a solve that did no work.
  polish_gain  the share of J that a plain local search still finds from
               the answer: Frank-Wolfe rounds on the routing with the hosts
               fixed (a convex multicommodity flow once the hosts are
               fixed), then each app re-placed by a dynamic programme over
               its partition chain on the marginal link costs (Eqs. 12-13)
               and the exact compute costs, then Frank-Wolfe again. J of
               every iterate is exact, so the gain is one that exists. An
               answer whose ALT rounds stopped early leaves more of it.

`control_answer` is the control: the same model evaluated with phi and every
intermediate stored in bfloat16, the step below the float32 that the
configuration states, put in the program's place.
"""
from __future__ import annotations

import numpy as np

FW_ITERS = 20  # Frank-Wolfe rounds before and after the re-placement


def _round(x, rounding):
    if rounding is None:
        return np.asarray(x, np.float64)
    import ml_dtypes

    return np.asarray(x, np.float64).astype(getattr(ml_dtypes, rounding)).astype(np.float64)


def mm1(load, cap, rho):
    cap = np.maximum(cap, 1e-9)
    knee = rho * cap
    gap = cap - knee
    v, s, c = knee / gap, cap / gap**2, 2.0 * cap / gap**3
    d = load - knee
    safe = np.minimum(load, knee)
    return np.where(load <= knee, safe / (cap - safe), v + s * d + 0.5 * c * d * d)


def mm1_prime(load, cap, rho):
    cap = np.maximum(cap, 1e-9)
    knee = rho * cap
    gap = cap - knee
    s, c = cap / gap**2, 2.0 * cap / gap**3
    safe = np.minimum(load, knee)
    return np.where(load <= knee, cap / (cap - safe) ** 2, s + c * (load - knee))


def _placement(inst, hosts):
    A, P = inst.n_apps, inst.w.shape[1]
    x = np.zeros((A, P, inst.n_nodes))
    x[np.arange(A)[:, None], np.arange(P)[None, :], hosts] = 1.0
    return x


def _traffic(inst, x, phi, rounding=None):
    """Stage traffic t [A,K,n], link load F, node load G and J (Eqs. 3-7)."""
    A, P = x.shape[:2]
    K, n = P + 1, inst.n_nodes
    lam = np.asarray(inst.lam, np.float64)
    L = np.asarray(inst.L, np.float64)
    w = np.asarray(inst.w, np.float64)
    _, rho, w_comm, w_comp = inst.cost
    eye = np.eye(n)
    t = np.zeros((A, K, n))
    prev = np.zeros((A, n))
    for k in range(K):
        if k == 0:
            b = np.zeros((A, n))
            b[np.arange(A), inst.src] = lam
        else:
            b = x[:, k - 1] * prev
        a = eye - np.swapaxes(phi[:, k], -1, -2)
        prev = _round(np.linalg.solve(a, b[..., None])[..., 0], rounding)
        t[:, k] = prev
    F = _round(np.einsum("ak,aki,akij->ij", L, t, phi), rounding)
    G = _round(np.einsum("ap,api,api->i", w, x, t[:, :P]), rounding)
    mu = np.asarray(inst.mu, np.float64)
    nu = np.asarray(inst.nu, np.float64)
    D = _round(np.where(np.asarray(inst.adj) > 0, mm1(F, mu, rho), 0.0), rounding)
    C = _round(mm1(G, nu, rho), rounding)
    J = float(_round(w_comm * D.sum() + w_comp * C.sum(), rounding))
    return t, F, G, J


def control_answer(inst, hosts, phi):
    """The control's answer for one instance: phi rounded to bfloat16 and
    J evaluated in bfloat16 (module doc)."""
    n, A, P = inst.n_nodes, inst.n_apps, inst.w.shape[1]
    hosts = np.asarray(hosts)[:A, :P].astype(np.int64)
    phi16 = _round(np.asarray(phi)[:A, : P + 1], "bfloat16")
    sub = np.where(np.asarray(inst.adj)[None, None] > 0, phi16[:, :, :n, :n], 0.0)
    _, _, _, J = _traffic(inst, _placement(inst, hosts), sub, "bfloat16")
    return phi16, J


def check(inst, hosts, phi, J) -> dict:
    """The reference's consistency numbers for one answer (module doc)."""
    n, A = inst.n_nodes, inst.n_apps
    P = inst.w.shape[1]
    K = P + 1
    hosts = np.asarray(hosts)[:A, :P].astype(np.int64)
    phi_env = np.asarray(np.asarray(phi)[:A, :K], np.float64)
    out = {"bad_hosts": int(((hosts < 0) | (hosts >= n)).sum()), "stray": 0.0,
           "mass_err": 0.0, "J_gap": float("inf")}
    if out["bad_hosts"]:
        return out

    adj = np.asarray(inst.adj) > 0
    allowed = np.zeros(phi_env.shape[-2:], bool)
    allowed[:n, :n] = adj
    rows = np.zeros(phi_env.shape[-2], bool)
    rows[:n] = True
    lam_live = (np.asarray(inst.lam) > 0)[:, None, None, None]
    sent = np.where(rows[None, None, :, None] & lam_live, phi_env, 0.0)
    out["stray"] = float(np.abs(np.where(allowed, 0.0, sent)).sum())
    if not np.isfinite(phi_env).all() or (sent < 0).any():
        out["stray"] = float("inf")

    phi = np.where(adj[None, None] & lam_live, phi_env[:, :, :n, :n], 0.0)
    x = _placement(inst, hosts)
    dst = np.zeros((A, n))
    dst[np.arange(A), inst.dst] = 1.0
    mass = np.concatenate([1.0 - x, (1.0 - dst)[:, None]], axis=1)  # [A,K,n]
    mass = mass * (np.asarray(inst.lam) > 0)[:, None, None]
    out["mass_err"] = float(np.abs(phi.sum(-1) - mass).max())

    J_ref = _traffic(inst, x, phi)[3]
    out["J_gap"] = abs(float(J) - J_ref) / max(abs(J_ref), 1e-30)
    return out


def _apsp(weight):
    """Floyd-Warshall over a [n, n] weight matrix (inf off the edges)."""
    d = np.where(np.isfinite(weight), weight, np.inf)
    np.fill_diagonal(d, 0.0)
    for k in range(d.shape[0]):
        d = np.minimum(d, d[:, k, None] + d[None, k, :])
    return d


def _next_hop(weight, d):
    """[i, target] -> the neighbour j minimising weight_ij + d(j, target)."""
    return np.argmin(weight[:, :, None] + d[None, :, :], axis=1)


def _chain(d, L, src, dst, node_cost):
    """Hosts [P] minimising L_0 d(src, h_0) + sum_p node_cost[p, h_p] +
    L_p d(h_{p-1}, h_p) + L_P d(h_{P-1}, dst): a dynamic programme over the
    partition chain; ties go to the lowest index."""
    P = node_cost.shape[0]
    M = L[0] * d[src] + node_cost[0]
    ptrs = []
    for p in range(1, P):
        cand = M[:, None] + L[p] * d
        ptrs.append(np.argmin(cand, axis=0))
        M = cand.min(axis=0) + node_cost[p]
    hosts = np.zeros(P, np.int64)
    hosts[P - 1] = int(np.argmin(M + L[P] * d[:, dst]))
    for p in range(P - 2, -1, -1):
        hosts[p] = int(ptrs[p][hosts[p + 1]])
    return hosts


def start_cost(inst) -> float:
    """J of the paper's zero-load start (section IV, method a): hosts that
    minimise the congestion-free path-plus-compute cost, and shortest-path
    forwarding trees under the zero-load link marginals. Ties go to the
    lowest index; a solver that optimises from such a start ends at or
    below it."""
    n, A, P = inst.n_nodes, inst.n_apps, inst.w.shape[1]
    _, rho, w_comm, w_comp = inst.cost
    adj = np.asarray(inst.adj) > 0
    mu = np.asarray(inst.mu, np.float64)
    nu = np.asarray(inst.nu, np.float64)
    dp0 = np.where(adj, w_comm * mm1_prime(np.zeros_like(mu), mu, rho), np.inf)
    d = _apsp(dp0)
    kappa0 = np.asarray(inst.w, np.float64)[:, :, None] * (
        w_comp * mm1_prime(np.zeros_like(nu), nu, rho)
    )[None, None, :]
    L = np.asarray(inst.L, np.float64)
    hosts = np.stack([_chain(d, L[a], inst.src[a], inst.dst[a], kappa0[a])
                      for a in range(A)])
    nxt = _next_hop(dp0, d)
    targets = np.concatenate([hosts, np.asarray(inst.dst)[:, None]], axis=1)
    phi = np.zeros((A, P + 1, n, n))
    rows = np.arange(n)
    for a in range(A):
        if inst.lam[a] <= 0:
            continue
        for k in range(P + 1):
            tgt = targets[a, k]
            src_rows = rows[rows != tgt]
            phi[a, k, src_rows, nxt[src_rows, tgt]] = 1.0
    return _traffic(inst, _placement(inst, hosts), phi)[3]


class _Flows:
    """An instance as a multicommodity flow once the hosts are fixed: app
    a's stage k carries lam_a from ends[a, k] to ends[a, k + 1] (the source,
    the P hosts, the destination), and f [A, K, n, n] holds each stage's
    link flows in traffic units."""

    def __init__(self, inst):
        self.inst = inst
        _, self.rho, self.w_comm, self.w_comp = inst.cost
        self.adj = np.asarray(inst.adj) > 0
        self.mu = np.asarray(inst.mu, np.float64)
        self.nu = np.asarray(inst.nu, np.float64)
        self.L = np.asarray(inst.L, np.float64)
        self.w = np.asarray(inst.w, np.float64)
        self.lam = np.asarray(inst.lam, np.float64)

    def ends(self, hosts):
        return np.concatenate([self.inst.src[:, None], hosts, self.inst.dst[:, None]], 1)

    def node_load(self, hosts):
        G = np.zeros(self.inst.n_nodes)
        np.add.at(G, hosts, self.w * self.lam[:, None])
        return G

    def link_load(self, f):
        return np.einsum("ak,akij->ij", self.L, f)

    def link_cost(self, F):
        return self.w_comm * np.where(self.adj, mm1(F, self.mu, self.rho), 0.0).sum()

    def cost(self, hosts, f):
        comp = self.w_comp * mm1(self.node_load(hosts), self.nu, self.rho).sum()
        return self.link_cost(self.link_load(f)) + comp

    def marginal(self, F):
        return np.where(self.adj, self.w_comm * mm1_prime(F, self.mu, self.rho), np.inf)

    def shortest(self, ends, weight, apps):
        """The listed apps' stages on their shortest paths under weight:
        lam_a enters at the stage's start and follows the next hops toward
        its end, y [A, K, n, n] in traffic units (zero for other apps)."""
        A, n = self.inst.n_apps, self.inst.n_nodes
        apps = np.asarray(apps, np.int64)
        nxt = _next_hop(weight, _apsp(weight))
        tgt = ends[apps, 1:]  # [a, K]
        ai, ki, ii = np.indices(tgt.shape + (n,))
        hop = np.zeros(tgt.shape + (n, n))
        hop[ai, ki, ii, nxt[ii, tgt[ai, ki]]] = 1.0
        hop *= (ii != tgt[..., None])[..., None]  # the stage ends at its target
        b = np.zeros(tgt.shape + (n,))
        np.put_along_axis(b, ends[apps, :-1, None], self.lam[apps, None, None], -1)
        t = np.linalg.solve(np.eye(n) - np.swapaxes(hop, -1, -2), b[..., None])[..., 0]
        y = np.zeros((A, tgt.shape[1], n, n))
        y[apps] = t[..., None] * hop
        return y

    def frank_wolfe(self, hosts, f, iters):
        ends = self.ends(hosts)
        live = np.flatnonzero(self.lam > 0)
        mu = self.mu[self.adj]
        for _ in range(iters):
            F = self.link_load(f)
            y = self.shortest(ends, self.marginal(F), live)
            step = (self.link_load(y) - F)[self.adj]
            F = F[self.adj]
            lo, hi = 0.0, 1.0  # exact line search: the link cost is convex
            for _ in range(24):
                g = 0.5 * (lo + hi)
                slope = (mm1_prime(F + g * step, mu, self.rho) * step).sum()
                lo, hi = (lo, g) if slope > 0 else (g, hi)
            f = f + lo * (y - f)
        return f

    def replace(self, hosts, f):
        """Each app in turn: its flows taken out, its hosts re-chosen by
        `_chain` on the marginal link costs of the rest and the exact
        compute cost of adding it, its stages on the shortest paths."""
        hosts, f = hosts.copy(), f.copy()
        for a in np.flatnonzero(self.lam > 0):
            f_rest = f.copy()
            f_rest[a] = 0.0
            weight = self.marginal(self.link_load(f_rest))
            G = self.node_load(hosts)
            np.subtract.at(G, hosts[a], self.w[a] * self.lam[a])
            add = self.w[a][:, None] * self.lam[a]
            node_cost = self.w_comp * (mm1(G[None] + add, self.nu, self.rho)
                                       - mm1(G[None], self.nu, self.rho))
            d = _apsp(weight)
            new = _chain(d, self.lam[a] * self.L[a], self.inst.src[a], self.inst.dst[a],
                         node_cost)
            if not np.array_equal(new, hosts[a]):
                hosts[a] = new
                f = f_rest + self.shortest(self.ends(hosts), weight, [a])
        return hosts, f


def polish_gain(inst, hosts, phi) -> float:
    """(J - J_polished) / J for one answer (module doc)."""
    n, A, P = inst.n_nodes, inst.n_apps, inst.w.shape[1]
    hosts = np.asarray(hosts)[:A, :P].astype(np.int64)
    adj = np.asarray(inst.adj) > 0
    phi = np.where(adj[None, None], np.asarray(phi, np.float64)[:A, : P + 1, :n, :n], 0.0)
    t = _traffic(inst, _placement(inst, hosts), phi)[0]
    flows = _Flows(inst)
    f = t[..., None] * phi
    J0 = flows.cost(hosts, f)
    f1 = flows.frank_wolfe(hosts, f, FW_ITERS)
    h2, f2 = flows.replace(hosts, f1)
    f2 = flows.frank_wolfe(h2, f2, FW_ITERS)
    best = min(flows.cost(hosts, f1), flows.cost(h2, f2))
    return float((J0 - best) / J0)
