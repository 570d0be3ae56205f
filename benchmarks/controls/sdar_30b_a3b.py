"""What the comparisons of ``runners/block_diffusion_train.py`` read when
the plain reference is computed with a term wrong or a precision below the
one the configuration states: the second reading every limit of the cell
is set from.

    python3 benchmarks/controls/sdar_30b_a3b.py --seed <n> [--rehearse] \
        [--out FILE] [--only fault,fault]

At the cell's sizes on the chip (``--rehearse``: its rehearsal sizes on the
CPU). The parameters are the cell's and the first batch the cell's for that seed,
and the readings are taken by the runner's own functions: the reference as
stated against itself with each fault of ``FAULTS``, through (a) and (b);
and the same with rounded operands against the rounded reference, through
(c) and (d). Prints one JSON object: for each fault every reading and
``outside``, the limits it falls outside. A fault whose ``outside`` is
empty is one the cell cannot see.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = os.path.splitext(os.path.basename(__file__))[0]
TRAFFIC = "train_bd_s4096"          # the configuration's one cell
# fault -> the switch of the reference's ``lower`` that plants it
FAULTS = {
    "plain_causal_mask": "causal_mask",
    "noisy_rows_read_their_blocks_clean_copy": "block_leak",
    "clean_rows_strictly_causal": "clean_strict",
    "noisy_rows_at_position_r": "noisy_position",
    "no_loss_weight": "no_weight",
    "loss_over_all_noisy_positions": "all_positions",
    "no_qk_norm": "no_qk_norm",
    "sigmoid_router": "sigmoid_router",
    "no_renormalisation": "no_renorm",
    "bfloat16_router": "router_bf16",
    "ungated_experts": "ungated",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated faults (default: all)")
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
    from benchmarks.runners import block_diffusion_train as runner
    from paddlebox_tpu.parallel import HybridTopology, build_mesh

    config = load_json("configs", CONFIG + ".json")
    traffic = load_json("traffic", TRAFFIC + ".json")
    if args.rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    reference = importlib.import_module("benchmarks.reference." + CONFIG)
    cfg = runner.program_config(config)
    per_piece = cfg.num_hidden_layers // cfg.pieces
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    key = jax.random.PRNGKey(args.seed)
    params, specs = runner.init_params(
        cfg, jax.random.PRNGKey(config["weights_seed"]),
        NamedSharding(mesh, P()))
    batch = runner.batch_draw(
        key, config, float(traffic["zipf_a"]),
        int(config["sequences_per_chip"]), int(traffic["sequence_length"]),
        NamedSharding(mesh, P("dp")))(0)
    # the experts the cell compares are chosen from the program's own
    # gradient, as the runner chooses them
    with jax.default_matmul_precision("highest"):
        experts = runner.program_reading(
            cfg, mesh, specs, runner.checked_leaves(cfg.pieces, per_piece))(
            params, *batch)[2]
    paths = runner.checked_leaves(cfg.pieces, per_piece,
                                  [int(e) for e in experts])
    read = runner.reference_reading(reference, config, paths)
    updates = runner.first_updates(reference, config["learning_rate"])
    picked = [runner.leaf_at(params, path) for path in paths]
    rounded = dict(reference.STATED, operands=not args.rehearse)

    def reading(lower):
        """(loss, aux, gradients, their first updates), on the host."""
        (loss, aux), grads = read(picked, params, *batch, lower)
        update = jax.device_get(updates(grads, picked))
        return (float(loss), runner.host_aux(aux), jax.device_get(grads),
                update)

    want = reading(reference.STATED)
    want_timed = reading(rounded)
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "leaves": [".".join(map(str, path)) for path in paths],
           "sequence_length": int(traffic["sequence_length"]),
           "reference_loss": want[0], "reference_aux": want[1]}
    chosen = args.only.split(",") if args.only else list(FAULTS)
    for name in chosen:
        switch = FAULTS[name]
        got = reading(dict(reference.STATED, **{switch: True}))
        grad_err = runner.grad_errors(paths, got[2], want[2])
        routing = runner.routing_shares(got[1]["load"], want[1]["load"])
        got_timed = reading(dict(rounded, **{switch: True}))
        update_err = runner.grad_errors(paths, got_timed[3], want_timed[3])
        step_routing = runner.routing_shares(got_timed[1]["load"],
                                             want_timed[1]["load"])
        out[name] = {
            "loss": got[0], "masked": got[1]["masked"],
            "weight": got[1]["weight"], "grad_rel_err": grad_err,
            "routing_share_pooled": routing[1],
            "update_rel_err": update_err,
            "step_routing_share_pooled": step_routing[1],
            "outside": runner.outside(got[0], want[0], got[1], want[1],
                                      grad_err, routing, per_piece)
            + runner.outside_timed(update_err, step_routing, per_piece)}
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
