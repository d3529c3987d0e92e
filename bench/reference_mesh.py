"""Plain float64 reference of the distributed sampler's sweeps.

Nothing here imports the program. The distributed sampler (paper Sec 4)
draws from the same conditionals as the single-chip one, with three
differences in what it documents:

- its key schedule: each sweep splits the chain key into (next, hyper of v,
  v, hyper of u, u), and the chain key is the third of three splits of
  `PRNGKey(init seed)`;
- its noise: entity i of a half-sweep draws `normal(fold_in(key, i), (K,))`,
  keyed by the entity's global id, whatever shard holds it;
- its exchange mode: in `ring` and `allgather` the user half reads the
  item factors the same sweep drew; in `async` it reads the previous
  sweep's (stale by one), and the state carries that v as `v_eval`.

Both Normal-Wishart draws of a sweep read the previous sweep's factors in
every mode. The Normal-Wishart draw and the conditional draw are
`reference.py`'s, in float64, from `jax.random` variates; so is the float8
control.
"""
from __future__ import annotations

import numpy as np

import reference as ref
from harness import Compare


def chain_key(init_seed: int):
    """The chain key a state initialised from `init_seed` starts from."""
    import jax

    return jax.random.split(jax.random.PRNGKey(init_seed), 3)[2]


def folded_rows(key, rows: np.ndarray, k: int) -> np.ndarray:
    """The noise of entities `rows`: a standard normal (K,) per entity from
    the key folded with its global id."""
    import jax

    return np.stack([np.asarray(jax.random.normal(jax.random.fold_in(key, int(i)),
                                                  (k,), np.float32), np.float64)
                     for i in rows])


def check(cfg: dict, sides: dict, init: dict, states: list[dict],
          init_seed: int, limits: dict, control: bool = False
          ) -> tuple[list[Compare], list[Compare] | None]:
    """Each checked sweep, half by half, from the state it was handed.

    `sides` holds, for "u" and "v", the entities to check and the centred
    training ratings grouped by entity (indptr, counterpart ids, values);
    `init` the initial state and `states` the state after each checked
    sweep, in global entity order (`u`, `v`, `v_eval` or None, `mu_u`,
    `lam_u`, `mu_v`, `lam_v`). Returns the program's gaps and, with
    `control`, the float8 control's on the same inputs."""
    k, alpha = int(cfg["k"]), float(cfg["alpha"])
    stale = cfg["mesh"]["mode"] == "async"
    key = chain_key(init_seed)
    u_prev, v_prev = init["u"], init["v"]
    gaps = {"program": [0.0, 0.0], "control": [0.0, 0.0]}
    for st in states:
        keys = ref.sweep_keys(key)
        key = keys.next
        for side, other, hk, zk in (
            ("v", u_prev, keys.hyper_v, keys.v),
            ("u", v_prev if stale else st["v"], keys.hyper_u, keys.u),
        ):
            rows, indptr, cols, vals = sides[side]
            x_prev = u_prev if side == "u" else v_prev
            var = ref.nw_variates(hk, k, x_prev.shape[0])
            mu_r, lam_r = ref.normal_wishart(x_prev, var)
            z = folded_rows(zk, rows, k)
            want = ref.conditional_draws(other, indptr, cols, vals, rows, mu_r,
                                         lam_r, alpha, z)
            outputs = {"program": (st[f"mu_{side}"], st[f"lam_{side}"], st[side][rows])}
            if control:
                mu_c, lam_c = ref.normal_wishart(x_prev, var, control=True)
                outputs["control"] = (mu_c, lam_c, ref.conditional_draws(
                    other, indptr, cols, vals, rows, mu_c, lam_c, alpha, z,
                    control=True))
            for who, (mu_p, lam_p, got) in outputs.items():
                g = gaps[who]
                g[0] = max(g[0], ref.draw_gap(got, want))
                g[1] = max(g[1], ref.hyper_gap(mu_p, lam_p, mu_r, lam_r))
        if stale:
            # the state names the v its u half read, which predictions
            # pair with u: the previous sweep's
            rows = sides["v"][0]
            got = st["v"] if st["v_eval"] is None else st["v_eval"]
            g = gaps["program"]
            g[0] = max(g[0], ref.draw_gap(got[rows], v_prev[rows]))
        u_prev, v_prev = st["u"], st["v"]

    def compares(g):
        return [Compare("draw_gap", g[0], limits["draw_gap"]),
                Compare("hyper_gap", g[1], limits["hyper_gap"])]

    return compares(gaps["program"]), compares(gaps["control"]) if control else None
