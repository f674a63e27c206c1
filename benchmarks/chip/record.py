"""What the comparison with the plain reference reads, and the comparison.

A record holds, for the first ``check_steps`` steps of a run:

* ``losses``: each step's losses (any shape; their mean is compared);
* ``grad``: per parameter leaf, the norm of the gradient as the optimizer
  got it, worked out from the optimizer's state after the first step;
* ``change``: per parameter leaf, the norm of the parameters' change over
  the steps.

Norms are compared, not differences: each leaf's gap is
``|program norm - reference norm|`` over the larger of the reference's
norm of that leaf and the reference's median leaf norm.  Leaves whose
reference gradient is under a thousandth of the median leaf's are left
out of ``grad`` and ``change``: they move by round-off alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUIET = 1e-3   # a leaf's gradient under this share of the median: left out


def leaf_names(tree, prefix=""):
    """``{"a/b/c": leaf}`` for a nested dict (or NamedTuple) tree."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = [str(getattr(k, "key", getattr(k, "name", getattr(k, "idx",
                                                                  k))))
                 for k in path]
        out["/".join([prefix] + parts if prefix else parts)] = leaf
    return out


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def norms(tree):
    """``{leaf name: float norm}``."""
    return {k: float(v) for k, v in leaf_names(_norms(tree)).items()}


def _identity(tree):
    return tree


def change_norms(params, init, view=_identity):
    """Per leaf of ``view(params)``, the norm of its change from
    ``view(init)``; ``view`` relabels a tree inside the jitted call, so a
    layout change costs no copy."""
    fn = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))),
        view(a), view(b)))
    return {k: float(v) for k, v in leaf_names(fn(params, init)).items()}


def rms_grad_norms(opts, b2):
    """``opts``: ``{name: (nu tree with leading member axis, (N,) step
    counts)}``.  Per leaf, the norm of ``sqrt(nu / (1 - b2**t))``: each
    member's root-mean-square gradient over its steps so far."""
    out = {}
    for name, (nu, t) in opts.items():
        corr = 1.0 - b2 ** np.asarray(t, np.float64)
        corr = jnp.asarray(np.where(corr > 0, corr, 1.0), jnp.float32)
        rms = jax.tree.map(
            lambda v: jnp.sqrt(v / corr.reshape((-1,) + (1,) * (v.ndim - 1))),
            nu)
        out.update(norms({name: rms}))
    return out


def first_grad_norms(mu, b1, view=_identity):
    """Per leaf of ``view(mu)``, the norm of the first gradient from
    Adam's first moment after one step: ``mu = (1 - b1) g``."""
    fn = jax.jit(lambda m: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x / (1.0 - b1)))), view(m)))
    return {k: float(v) for k, v in leaf_names(fn(mu)).items()}


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def _leaf_gaps(prog, ref, keep):
    floor = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in keep}


def _worst_leaf(prog, ref, keep):
    gaps = _leaf_gaps(prog, ref, keep)
    return max(gaps.values()), max(gaps, key=gaps.get)


def compare(prog, ref):
    """The numbers compared, ``{name: (value, detail)}``."""
    missing = set(ref["grad"]) ^ set(prog["grad"])
    if missing or set(ref["change"]) != set(prog["change"]):
        raise ValueError(f"records differ in leaves: {sorted(missing)}")
    med = float(np.median(list(ref["grad"].values())))
    keep = [k for k, v in ref["grad"].items() if v >= QUIET * med]
    steps = len(ref["losses"])
    if len(prog["losses"]) != steps:
        raise ValueError(f"{len(prog['losses'])} program steps against "
                         f"{steps} of the reference")
    loss = [_rel(float(np.mean(p)), float(np.mean(r)))
            for p, r in zip(prog["losses"], ref["losses"])]
    first = _rel(float(np.mean(prog["losses"][0][0])),
                 float(np.mean(ref["losses"][0][0])))
    grad, grad_leaf = _worst_leaf(prog["grad"], ref["grad"], keep)
    change, change_leaf = _worst_leaf(
        prog["change"], ref["change"],
        [k for k in keep if k in ref["change"]])
    change_keep = [k for k in keep if k in ref["change"]]
    out = {"loss_first": (first, "step 1, first row"),
           "loss": (max(loss), f"step {int(np.argmax(loss)) + 1}"),
           "loss_steps": (loss, "per step"),
           "grad": (grad, grad_leaf),
           "grad_median": (float(np.median(list(_leaf_gaps(
               prog["grad"], ref["grad"], keep).values()))), "median leaf"),
           "change": (change, change_leaf),
           "change_median": (float(np.median(list(_leaf_gaps(
               prog["change"], ref["change"], change_keep).values()))),
               "median leaf"),
           "_left_out": (len(ref["grad"]) - len(keep), "quiet leaves")}
    if np.ndim(ref["losses"][0]) == 2:
        p1 = np.asarray(prog["losses"][0][0], np.float64)
        r1 = np.asarray(ref["losses"][0][0], np.float64)
        gaps = np.abs(p1 - r1) / np.maximum(np.abs(r1), 1e-30)
        out["loss_first_member"] = (float(np.max(gaps)),
                                    f"member {int(np.argmax(gaps))}")
        out["loss_first_member_median"] = (float(np.median(gaps)),
                                           "median member")
        every = np.abs(np.asarray(prog["losses"][0], np.float64)
                       - np.asarray(ref["losses"][0], np.float64)) \
            / np.maximum(np.abs(np.asarray(ref["losses"][0], np.float64)),
                         1e-30)
        out["loss_epoch1_median"] = (float(np.median(every)),
                                     "step 1, median row and member")
        out["loss_rows_1"] = (
            [_rel(float(np.mean(p)), float(np.mean(r)))
             for p, r in zip(prog["losses"][0], ref["losses"][0])],
            "step 1 per row")
    if "lineage" in prog and "lineage" in ref:
        out["lineage_mismatch"] = (
            [int(np.sum(np.asarray(p) != np.asarray(r)))
             for p, r in zip(prog["lineage"], ref["lineage"])],
            "members whose PBT parent differs, per step")
    return out
