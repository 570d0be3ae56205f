"""What the comparisons of ``runners/hybrid_train.py`` read when the plain
reference is computed a precision below the one the configuration states,
or a term short: the second reading every limit of the cell is set from.

    python3 benchmarks/controls/nemotron3_super_120b.py --seed <n> \
        [--rehearse] [--out FILE]

At the cell's sizes on the chip (``--rehearse``: its rehearsal sizes on the
CPU). The parameters and the first batch are the cell's own for that seed,
and the readings are taken by the runner's own functions: the reference as
stated against itself with a bfloat16 scan state, a bfloat16 router, no
``D x`` skip in the second Mamba layer and no shared expert in the first
expert layer, through (a), (b), (c); and the same with rounded operands
against the rounded reference, through (e), (f). Prints one JSON object:
for each fault every reading and ``outside``, the limits it falls outside.
A fault whose ``outside`` is empty is one the cell cannot see.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = os.path.splitext(os.path.basename(__file__))[0]
TRAFFIC = "train_s8192"             # the configuration's one cell


def zeroed(params, layer: int, name: str):
    import jax.numpy as jnp
    layers = list(params["layers"])
    layers[layer] = dict(layers[layer],
                         **{name: jnp.zeros_like(layers[layer][name])})
    return dict(params, layers=layers)


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
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.run import load_json, overlay
    from benchmarks.runners import hybrid_train as runner
    from paddlebox_tpu.parallel import HybridTopology, build_mesh

    config = load_json("configs", CONFIG + ".json")
    traffic = load_json("traffic", TRAFFIC + ".json")
    if args.rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    reference = importlib.import_module("benchmarks.reference." + CONFIG)
    cfg = runner.program_config(config)
    pattern = cfg.pattern
    mesh = build_mesh(HybridTopology(dp=1), devices=jax.devices()[:1])
    key = jax.random.PRNGKey(args.seed)
    params, _ = runner.init_params(cfg, key, NamedSharding(mesh, P()))
    batch, seq = int(config["sequences_per_chip"]), int(
        traffic["sequence_length"])
    tokens, targets = runner.token_draw(
        key, config["vocab_size"], float(traffic["zipf_a"]), batch, seq,
        NamedSharding(mesh, P("dp")))(0)
    leaves = runner.checked_leaves(pattern)
    read = runner.reference_reading(reference, config, leaves)
    updates = runner.first_updates(reference, config["learning_rate"])
    sound = [runner.leaf_at(params, path) for path in leaves]
    rounded = dict(reference.STATED, operands=not args.rehearse)

    def reading(params, lower):
        """(loss, load, gradients, their first updates), on the host: the
        device has room for one reading's gradient at a time."""
        (loss, load), grads = read(
            [runner.leaf_at(params, path) for path in leaves], params,
            tokens, targets, lower)
        update = jax.device_get(updates(grads, sound))
        return float(loss), np.asarray(load), jax.device_get(grads), update

    want = reading(params, reference.STATED)
    want_timed = reading(params, rounded)
    mambas = [i for i, letter in enumerate(pattern) if letter == "M"]
    faults = {
        "bfloat16_scan_state": (params, {"state": True}),
        "bfloat16_router": (params, {"router": True}),
        "no_d_skip": (zeroed(params, mambas[min(1, len(mambas) - 1)], "d"),
                      {}),
        "no_shared_expert": (zeroed(params, pattern.index("E"), "ws2"), {}),
    }
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "sequence_length": seq, "reference_loss": want[0]}
    for name, (faulty, lower) in faults.items():
        got = reading(faulty, dict(reference.STATED, **lower))
        grad_err = runner.grad_errors(leaves, got[2], want[2])
        routing = runner.routing_shares(got[1], want[1])
        got_timed = reading(faulty, dict(rounded, **lower))
        update_err = runner.grad_errors(leaves, got_timed[3], want_timed[3])
        step_routing = runner.routing_shares(got_timed[1], want_timed[1])
        out[name] = {
            "loss": got[0], "grad_rel_err": grad_err,
            "routing_share_differing": routing[0],
            "routing_share_pooled": routing[1],
            "update_rel_err": update_err,
            "step_routing_share_differing": step_routing[0],
            "step_routing_share_pooled": step_routing[1],
            "outside": runner.outside(got[0], want[0], grad_err, routing,
                                      pattern)
            + runner.outside_timed(update_err, step_routing, pattern)}
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
