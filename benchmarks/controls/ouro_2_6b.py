"""What the comparisons of ``runners/looped_train.py`` read when the plain
reference is computed a term short or a precision below the one the
configuration states: the second reading every limit of the cell is set
from.

    python3 benchmarks/controls/ouro_2_6b.py --seed <n> [--rehearse] \
        [--out FILE]

At the cell's sizes on the chip (``--rehearse``: its rehearsal sizes on the
CPU). The parameters and the first batch are the cell's own for that seed,
and the readings are taken by the runner's own functions: the reference as
stated against itself with each fault of ``FAULTS``, through (a) and (b);
and the same with rounded operands against the rounded reference, through
(c). Prints one JSON object: for each fault every reading and ``outside``,
the limits it falls outside. A fault whose ``outside`` is empty is one the
cell cannot see.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = os.path.splitext(os.path.basename(__file__))[0]
TRAFFIC = "train_s4096"             # the configuration's one cell
# fault -> the switch of the reference's ``lower`` that plants it
FAULTS = {
    "three_passes": "three_passes",
    "last_pass_weight_gradient_only": "last_pass_grad",
    "no_norm_between_passes": "no_pass_norm",
    "no_post_norms": "no_post_norm",
    "no_rotary": "no_rotary",
    "rotary_theta_10000": "theta_10k",
    "no_gate": "no_gate",
    "beta_0": "beta_0",
    "bfloat16_state_between_passes": "bfloat16_state",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        from paddlebox_tpu.core import flags
        flags.compilation_cache_dir()
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    import importlib

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.run import load_json, overlay
    from benchmarks.runners import looped_train as runner
    from paddlebox_tpu.parallel import HybridTopology, build_mesh

    config = load_json("configs", CONFIG + ".json")
    traffic = load_json("traffic", TRAFFIC + ".json")
    if args.rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    reference = importlib.import_module("benchmarks.reference." + CONFIG)
    cfg = runner.program_config(config)
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    key = jax.random.PRNGKey(args.seed)
    params, _ = runner.init_params(cfg, key, NamedSharding(mesh, P()))
    batch, seq = int(config["sequences_per_chip"]), int(
        traffic["sequence_length"])
    tokens, targets = runner.token_draw(
        key, config["vocab_size"], float(traffic["zipf_a"]), batch, seq,
        NamedSharding(mesh, P("dp")))(0)
    leaves = runner.checked_leaves(cfg.pieces)
    read = runner.reference_reading(reference, config, leaves)
    updates = runner.first_updates(reference, config["learning_rate"])
    picked = [runner.leaf_at(params, path) for path in leaves]
    rounded = dict(reference.STATED, operands=not args.rehearse)

    def reading(lower):
        """(loss, aux, gradients, their first updates), on the host."""
        (loss, aux), grads = read(picked, params, tokens, targets, lower)
        update = jax.device_get(updates(grads, picked))
        return (float(loss), runner.host_aux(aux), jax.device_get(grads),
                update)

    want = reading(reference.STATED)
    want_timed = reading(rounded)
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "sequence_length": seq, "reference_loss": want[0],
           "reference_aux": want[1]}
    for name, switch in FAULTS.items():
        got = reading(dict(reference.STATED, **{switch: True}))
        grad_err = runner.grad_errors(leaves, got[2], want[2])
        got_timed = reading(dict(rounded, **{switch: True}))
        update_err = runner.grad_errors(leaves, got_timed[3], want_timed[3])
        out[name] = {
            "loss": got[0], "aux": got[1], "grad_rel_err": grad_err,
            "update_rel_err": update_err,
            "outside": runner.outside(got[0], want[0], got[1], want[1],
                                      grad_err)
            + runner.outside_timed(update_err)}
        del got, got_timed
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
