"""What decides `correct` in a training cell: the first trial the window
proposed, as worker/train.py ran it (its logged losses and the parameters it
persisted, fetched through the client), against benchmark/reference/vit.py
carried through the same weights, data order, loss and AdamW updates at the
trial's own learning rate.

Numbers compared, each against a limit in the configuration's file:
- `loss_first_epoch_rel`: the gap between the two mean losses of the first
  epoch (8 steps), relative to the reference's;
- `change_worst_leaf_rel`, `change_median_leaf_rel`: for each leaf the gap
  between the norm of the program's change over the trial and the norm of
  the reference's (not the norm of their difference), against the
  reference's norm for that leaf or for the median leaf, whichever is
  larger; the worst leaf and the median leaf. Leaves whose first gradient in
  the reference is under a thousandth of the median leaf's are left out.
A trial that did not complete, or whose parameters did not move at all,
reads 1.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness

# the reference's names -> the path of the leaf in models/vit.py's tree
LEAVES = {
    "patch.kernel": ("patch", "kernel"), "patch.bias": ("patch", "bias"),
    "pos": ("pos",),
    "ln1.scale": ("blocks", "ln1", "scale"),
    "ln1.bias": ("blocks", "ln1", "bias"),
    "wq": ("blocks", "attn", "wq"), "wk": ("blocks", "attn", "wk"),
    "wv": ("blocks", "attn", "wv"), "wo": ("blocks", "attn", "wo"),
    "bo": ("blocks", "attn", "bo"),
    "ln2.scale": ("blocks", "ln2", "scale"),
    "ln2.bias": ("blocks", "ln2", "bias"),
    "w1.kernel": ("blocks", "mlp", "w1", "kernel"),
    "w1.bias": ("blocks", "mlp", "w1", "bias"),
    "w2.kernel": ("blocks", "mlp", "w2", "kernel"),
    "w2.bias": ("blocks", "mlp", "w2", "bias"),
    "ln_f.scale": ("ln_f", "scale"), "ln_f.bias": ("ln_f", "bias"),
    "head.kernel": ("head", "kernel"), "head.bias": ("head", "bias"),
}


def reference_cfg(cfg: dict) -> dict:
    """The configuration as the reference reads it. The template has no
    `# @cell` line for the MLP's width, so a file that states another than
    the program's cannot be run as it stands."""
    if cfg["intermediate_size"] != 4 * cfg["hidden_size"]:
        raise harness.BenchmarkError("models/transformer.py fixes the MLP at "
                                     "four times the hidden size")
    return {**cfg, "num_labels": cfg["assumed"]["num_labels"]}


def program_leaves(params_bytes: bytes) -> dict:
    """The persisted parameters, by the reference's names."""
    from rafiki_tpu.sdk.params import load_params

    tree = load_params(params_bytes)["params"]
    out = {}
    for name, path in LEAVES.items():
        leaf = tree
        for key in path:
            leaf = leaf[key]
        out[name] = np.asarray(leaf, np.float32)
    return out


def compare(program: dict, ref: dict) -> dict:
    """`program`: epoch_losses and change_norm by leaf (or None where the
    trial gave nothing). `ref`: reference.train()'s result."""
    if program is None:
        return {"loss_first_epoch_rel": 1.0, "change_worst_leaf_rel": 1.0,
                "change_median_leaf_rel": 1.0}
    loss = abs(program["epoch_losses"][0] - ref["epoch_losses"][0]) \
        / abs(ref["epoch_losses"][0])
    grads = ref["first_grad_norm"]
    floor = 1e-3 * float(np.median(list(grads.values())))
    names = [n for n in ref["change_norm"] if grads[n] >= floor]
    median_change = float(np.median([ref["change_norm"][n] for n in names]))
    gaps = [abs(program["change_norm"][n] - ref["change_norm"][n])
            / max(ref["change_norm"][n], median_change) for n in names]
    return {"loss_first_epoch_rel": float(loss),
            "change_worst_leaf_rel": float(max(gaps)),
            "change_median_leaf_rel": float(np.median(gaps)),
            "_worst_leaf": names[int(np.argmax(gaps))]}


def program_numbers(check: dict, seed31: int, cfg: dict) -> dict | None:
    trial = check["trial"]
    if trial is None or check["params"] is None or not trial["epochs"]:
        return None
    import jax

    leaves = program_leaves(check["params"])
    reference = harness.load_by_name("reference", cfg["reference"])
    w0 = jax.device_get(reference.make_weights(seed31, cfg))
    return {"epoch_losses": [e["loss"] for e in trial["epochs"]],
            "change_norm": {n: float(np.linalg.norm(
                (leaves[n] - np.asarray(w0[n])).ravel())) for n in leaves}}


def judge(cfg: dict, numbers: dict) -> dict:
    """The numbers compared, each beside the configuration's limit. The
    run's own trial, a control's and a fault's go through this same
    function."""
    return {name: {"value": numbers[name], "limit": limit}
            for name, limit in cfg["limits"].items()}


def check(cell: dict, ctx, result: dict) -> dict:
    cfg = reference_cfg(cell["config_data"])
    traffic = cell["traffic_data"]
    seed31 = ctx.seed % harness.SEED_MOD
    program = program_numbers(result["check"], seed31, cfg)
    if program is None:
        return judge(cfg, compare(None, None))
    ref = harness.load_by_name("reference", cfg["reference"]).train(
        seed31, cfg, result["check"]["x"], result["check"]["y"],
        float(result["check"]["trial"]["knobs"]["learning_rate"]),
        traffic["batch_size"], traffic["epochs"])
    numbers = compare(program, ref)
    result["check_info"] = {
        "learning_rate": result["check"]["trial"]["knobs"]["learning_rate"],
        "worst_leaf": numbers["_worst_leaf"],
        "program_epoch_losses": program["epoch_losses"],
        "reference_epoch_losses": ref["epoch_losses"]}
    return judge(cfg, numbers)
