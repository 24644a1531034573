"""The check of user-centric FL (strategy ``ucfl``, Eq. 8 over Eq. 9).

Both sides start from the same data, weights and seeds and run the set-up
trial's rounds. The reference is ``bench/references/ucfl.py``; it draws
uniform cohorts, so a traffic with another sampler, or with a FedConfig
field that changes what a round computes, needs a check of its own. The
numbers, each of the program against the float32 reference:

* ``w_gap``: the largest entry of |W - W_ref| (Eq. 9 W, through the Gram
  kernel);
* ``grad_gap``: the special round's gradients (the first gradient the
  parameter server gets), by the worst leaf: |norm - norm_ref| over the
  larger of the reference leaf's norm and the median leaf's;
* ``update_gap``: the same for the change of the client models over the
  rounds (cohort gather, local SGD, mix-scatter);
* ``row_gap``: the worst client's |change - change_ref| over the larger of
  its reference change and the median client's;
* ``loss_gap``: relative gap of the mean training loss of the final
  models, both read by the reference's forward pass;
* ``acc_gap``: the larger gap of the average and the worst client's test
  accuracy at the last evaluation.

Leaves whose reference gradient is under a thousandth of the median
leaf's are nought to rounding and left out of the leaf measures.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.check import changes, leaf_norms, norm_gap
from bench.references import ucfl as reference

REF_BLOCK = 8  # most clients the reference holds at a time
# the reference in the program's place, for the readings of bench/calibrate.py
CONTROLS = {"control_bf16": {"dtype": jnp.bfloat16},
            "fault_half_batch": {"fault": "half_batch"},
            "fault_misroute": {"fault": "misroute"}}
# FedConfig fields that leave what a round computes unchanged
SAME_RESULT = {"mesh", "shard_state"}


def outputs(strategy, hist) -> dict:
    """What the check compares, copied to the host: W, the special round's
    gradients per leaf, the client models, and the last evaluation."""
    state = hist.state
    params = {k: np.asarray(v)
              for k, v in strategy.eval_params(state).items()}
    grads = np.asarray(state["collab"]["full_grads"])
    leaves, off = {}, 0
    for k in sorted(params):
        size = int(np.prod(params[k].shape[1:]))
        leaves[k] = grads[:, off:off + size]
        off += size
    if off != grads.shape[1]:
        raise ValueError(f"gradient width {grads.shape[1]} is not the "
                         f"model's {off}")
    return {"W": np.asarray(state["W"]), "full_grads": leaves,
            "params": params, "avg_acc": hist.avg_acc[-1],
            "worst_acc": hist.worst_acc[-1]}


def _reference(cell, arrays, params0, key, pseed, *, dtype=jnp.float32,
               fault=None) -> dict:
    """The reference's run of the set-up trial, copied to the host."""
    conf, traf = cell.config, cell.traffic
    part = traf["participation"]
    extra = set(traf.get("fed_config", {})) - SAME_RESULT
    if (traf["strategy"] != "ucfl" or part.get("sampler") != "uniform"
            or set(part) != {"cohort_size", "sampler"} or extra
            or set(traf["strategy_args"]) != {"var_batch_size"}):
        raise ValueError(f"the ucfl reference does not model traffic "
                         f"{traf['name']!r}")
    block = min(conf["clients"], REF_BLOCK)
    out = reference.run(
        params0, arrays, key, pseed, rounds=traf["check_rounds"],
        cohort_size=part["cohort_size"], lr=conf["lr"],
        momentum=conf["momentum"], epochs=conf["local_epochs"],
        batch=conf["batch_size"],
        var_batch=traf["strategy_args"]["var_batch_size"], block=block,
        dtype=dtype, fault=fault)
    accs = np.asarray(reference.client_accuracies(
        out["params"], arrays["x_test"], arrays["y_test"], block=block))
    return {"W": np.asarray(out["W"]),
            "full_grads": {k: np.asarray(v)
                           for k, v in out["full_grads"].items()},
            "params": {k: np.asarray(v, np.float32)
                       for k, v in out["params"].items()},
            "avg_acc": float(accs.mean()), "worst_acc": float(accs.min()),
            "loss": _mean_loss(out["params"], arrays, block)}


def _mean_loss(params, arrays, block) -> float:
    """Mean training loss of client models, by the reference's forward."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    return float(jnp.mean(reference.client_losses(
        params, arrays["x"], arrays["y"], block=block)))


def numbers(cell, prog, arrays, params0, key, pseed, **ref_kw) -> dict:
    """The numbers of ``prog`` (from ``outputs``) against the float32
    reference; with ``ref_kw`` (``dtype``, ``fault``), of the reference
    computed so in the program's place."""
    ref = _reference(cell, arrays, params0, key, pseed)
    if ref_kw:
        prog = _reference(cell, arrays, params0, key, pseed, **ref_kw)
    else:
        block = min(cell.config["clients"], REF_BLOCK)
        prog = dict(prog, loss=_mean_loss(prog["params"], arrays, block))
    params0 = {k: np.asarray(v) for k, v in params0.items()}
    gnorm_ref = leaf_norms(ref["full_grads"])
    median = float(np.median(list(gnorm_ref.values())))
    keep = [k for k, v in gnorm_ref.items() if v >= 1e-3 * median]
    d_prog = changes(prog["params"], params0)
    d_ref = changes(ref["params"], params0)
    m = next(iter(d_ref.values())).shape[0]
    flat_prog = np.concatenate([d_prog[k].reshape(m, -1) for k in keep], 1)
    flat_ref = np.concatenate([d_ref[k].reshape(m, -1) for k in keep], 1)
    row_ref = np.linalg.norm(flat_ref, axis=1)
    row_diff = np.linalg.norm(flat_prog - flat_ref, axis=1)
    row_scale = np.maximum(row_ref, np.median(row_ref))
    return {
        "w_gap": float(np.max(np.abs(np.asarray(prog["W"], np.float64)
                                     - np.asarray(ref["W"], np.float64)))),
        "grad_gap": norm_gap(leaf_norms(prog["full_grads"]), gnorm_ref,
                             keep),
        "update_gap": norm_gap(leaf_norms(d_prog), leaf_norms(d_ref), keep),
        "row_gap": float(np.max(row_diff / np.maximum(row_scale, 1e-30))),
        "loss_gap": abs(prog["loss"] - ref["loss"]) / ref["loss"],
        "acc_gap": max(abs(prog["avg_acc"] - ref["avg_acc"]),
                       abs(prog["worst_acc"] - ref["worst_acc"])),
    }
