"""Plain reference of SD-FEEL training rounds, and the comparison that
decides ``correct`` for a training cell.

The protocol follows the paper (arXiv:2112.10313, Algorithm 1 and Lemma 1)
written out client by client: ``tau1`` local SGD steps on every client,
then the intra-cluster average ``W <- W V B``; after ``tau2`` such periods
the round ends with ``W <- W V P^alpha B``, ``P`` the eq-(5) mixing matrix
of the edge servers' graph.  Clients are grouped contiguously and weighed
equally, as the round scheduler weighs them.  Gradients come from the
configuration's own plain reference (``bench/configs/<config>.py``), in
float32; weights are kept in the configuration's storage dtype, each
update and each average computed in float32 and rounded once to it.
Imports nothing of the program under test.
"""
from __future__ import annotations

import numpy as np

LEAF_FLOOR = 1e-3  # leaves whose first gradient is under this share of the median leaf's


def ring(d: int) -> np.ndarray:
    a = np.zeros((d, d))
    for i in range(d):
        a[i, (i + 1) % d] = a[(i + 1) % d, i] = 1.0
    return a


TOPOLOGIES = {"ring": ring}


def mixing_matrix(adjacency: np.ndarray, ratios: np.ndarray) -> np.ndarray:
    """Eq. (5): P = I - 2 / (l_1 + l_{D-1}) L diag(m~)^-1, with l_1 and
    l_{D-1} the largest and second-smallest eigenvalues of L~."""
    lap = np.diag(adjacency.sum(1)) - adjacency
    s = np.diag(ratios ** -0.5)
    eig = np.sort(np.linalg.eigvalsh(s @ lap @ s))[::-1]
    d = len(ratios)
    return np.eye(d) - 2.0 / (eig[0] + eig[d - 2]) * lap @ np.diag(1.0 / ratios)


def transitions(clients: int, clusters: int, alpha: int, topology: str):
    """(T_intra, T_inter), each (C, C): column j holds client j's weights
    over the clients it averages."""
    g = clients // clusters
    v = np.zeros((clients, clusters))
    b = np.zeros((clusters, clients))
    for i in range(clients):
        v[i, i // g] = 1.0 / g
        b[i // g, i] = 1.0
    p = mixing_matrix(TOPOLOGIES[topology](clusters), np.full(clusters, 1.0 / clusters))
    return v @ b, v @ np.linalg.matrix_power(p, alpha) @ b


def follow(ref, cfg: dict, w0, source, *, clients: int, clusters: int, tau1: int,
           tau2: int, alpha: int, lr: float, topology: str, rounds: int,
           low: bool = False, fault: str | None = None) -> dict:
    """Run ``rounds`` SD-FEEL rounds from the single model ``w0`` on the
    batches ``source(k)`` (leaves ``(C, b, ...)``).

    Returns the mean loss over clients at each local iteration, the norm of
    each leaf's first gradient (over all clients), and each leaf's change
    after the first and after the last round (norm over all clients).
    ``fault`` plants one of ``half_batch`` (each client's loss over half its
    batch) or ``no_transition`` (every average left out).
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    store = ref.storage_dtype(cfg, low)
    grad = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(p, b, cfg, low)))
    up = jax.jit(lambda w: jax.tree.map(lambda a: a.astype(f32), w))  # float32 gradients
    update = jax.jit(lambda w, g: jax.tree.map(
        lambda a, b: (a.astype(f32) - lr * b).astype(store), w, g))
    # one output client's leaf: sum_c t[c] * w_c, elementwise in float32
    mix = jax.jit(lambda t, *ws: sum(t[c] * w.astype(f32) for c, w in enumerate(ws)).astype(store))
    sq_delta = jax.jit(lambda w, w0: jax.tree.map(
        lambda a, b: jnp.sum(jnp.square(a.astype(f32) - b.astype(f32))), w, w0))
    sq_norm = jax.jit(lambda g: jax.tree.map(lambda a: jnp.sum(jnp.square(a)), g))
    t_intra, t_inter = (jnp.asarray(t, f32) for t in
                        transitions(clients, clusters, alpha, topology))

    def average(ws, t):
        """The clients' models mixed by ``t``, leaf by leaf; empties ``ws`` so
        that each old leaf is freed once its mixes are made."""
        leaves = [jax.tree.leaves(w) for w in ws]
        treedef = jax.tree.structure(ws[0])
        ws.clear()
        out = []
        for i in range(len(leaves[0])):
            old = [leaves[c][i] for c in range(clients)]
            out.append([mix(t[:, d], *old) for d in range(clients)])
            for c in range(clients):
                leaves[c][i] = None
            del old
        return [jax.tree.unflatten(treedef, [o[c] for o in out]) for c in range(clients)]

    def change(ws):
        total = None
        for w in ws:
            sq = sq_delta(w, w0)
            total = sq if total is None else jax.tree.map(jnp.add, total, sq)
        return np.sqrt(np.asarray(jax.tree.leaves(total), np.float64))

    def client_batch(batch, c):
        b = {k: jnp.asarray(v[c]) for k, v in batch.items()}
        if fault == "half_batch":
            n = b[next(iter(b))].shape[0]
            if n > 1:
                b = {k: v[: n // 2] for k, v in b.items()}
            else:  # one sequence: half of its positions
                b = {k: v[:, : v.shape[1] // 2] for k, v in b.items()}
        return b

    w0 = jax.tree.map(lambda a: a.astype(store), w0)
    ws = [w0] * clients
    losses, g1, out, k = [], None, {}, 0
    for r in range(1, rounds + 1):
        for _ in range(tau2):
            for _ in range(tau1):
                k += 1
                batch = source(k)
                step = []
                for c in range(clients):
                    loss, g = grad(up(ws[c]), client_batch(batch, c))
                    if k == 1:
                        sq = sq_norm(g)
                        g1 = sq if g1 is None else jax.tree.map(jnp.add, g1, sq)
                    ws[c] = update(ws[c], g)
                    step.append(float(loss))
                losses.append(float(np.mean(step)))
            if fault != "no_transition":
                ws = average(ws, t_intra)
        if fault != "no_transition":
            ws = average(ws, t_inter)
        if r == 1:
            out["change1"] = change(ws)
    out["change_last"] = change(ws)
    out["losses"] = np.asarray(losses)
    out["grad1"] = np.sqrt(np.asarray(jax.tree.leaves(g1), np.float64))
    out["leaves"] = [jax.tree_util.keystr(p) for p, _ in
                     jax.tree_util.tree_flatten_with_path(w0)[0]]
    return out


def norm_gap(got: np.ndarray, want: np.ndarray, keep: np.ndarray) -> tuple[float, int]:
    """Largest gap between two per-leaf norms over the kept leaves, against
    the reference's norm of that leaf or of the median kept leaf, whichever
    is larger.  Returns (gap, index of the worst leaf)."""
    idx = np.nonzero(keep)[0]
    floor = np.median(want[idx])
    gaps = np.abs(got[idx] - want[idx]) / np.maximum(want[idx], floor)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), int(idx[worst])


def compare(program: dict, reference: dict) -> dict:
    """The numbers compared for a training cell, each with what it read.

    ``loss_gap``: largest relative gap of the per-iteration mean loss.
    ``round1_change_gap`` and ``change_gap``: largest gap of a leaf's change
    after the first round and after the last (see ``norm_gap``).  Leaves
    whose reference first gradient is under ``LEAF_FLOOR`` of the median
    leaf's move by round-off alone and are left out.
    """
    lp, lr = np.asarray(program["losses"]), reference["losses"]
    g = reference["grad1"]
    keep = g >= LEAF_FLOOR * np.median(g)
    c1, w1 = norm_gap(np.asarray(program["change1"]), reference["change1"], keep)
    c3, w3 = norm_gap(np.asarray(program["change_last"]), reference["change_last"], keep)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    names = reference["leaves"]
    return {
        "numbers": {"loss_gap": loss_gap, "round1_change_gap": c1, "change_gap": c3},
        "worst_leaf": {"round1_change_gap": names[w1], "change_gap": names[w3]},
        "left_out": [n for n, k in zip(names, keep) if not k],
    }
