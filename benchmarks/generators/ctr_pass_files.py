"""Day-loop traffic for the CTR cells: pass files of svm-format text lines.

One general generator; a traffic mix is a parameter file in
``benchmarks/traffic/`` naming this module. Everything is a function of
``(seed, pass index, file index)``, so files are written by a pool of
processes with no shared arrays, and the same seed gives the same bytes.

Key space (per run, ``chips`` scales every count):

- resident keys ``1 .. N``: in the store before the first pass. A rank is
  mapped to a key by a bijection ``(rank * A + B) % N + 1`` (A coprime to
  N), so a pass's keys are scattered over the store's rows the way hashed
  feasigns are, with no table of N keys in memory;
- a pass's working set, by rank: ``hot`` planted keys (slot 0 only, the
  same in every pass), then ``core`` keys shared by every pass (what
  consecutive passes have in common), then keys only this pass touches,
  the last ``unseen`` of which lie beyond N (the store has never seen
  them);
- slots 1.. draw ranks over the working set without the hot head:
  uniformly, or Zipf(a)-ranked (``zipf_a``) so that the most drawn keys
  are the shared core, as in a log whose popular items persist.

Labels carry a planted signal on slot 0's key (bench.py:_planted_labels):
a learner that serves an embedding from the wrong row cannot reach the
ceiling ``auc_ceiling`` computes.

Lines are fixed width (keys zero-padded to 9 digits, dense features
``0.dddd``), which lets numpy fill a byte matrix instead of joining
strings: bench.py's np.char version takes seconds per 16k lines.
"""

from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

KEY_DIGITS = 9
DENSE_DIGITS = 4
_MULT = 2654435761          # Knuth's multiplicative constant, a prime


def plan(traffic: Dict, config: Dict, chips: int) -> Dict:
    """Sizes of one run, from the traffic mix and the configuration."""
    model = config["model"]
    n_resident = int(config["store"]["resident_keys_per_chip"]) * chips
    pass_keys = int(traffic["pass_keys_per_chip"]) * chips
    unseen = int(traffic["unseen_keys_per_pass_per_chip"]) * chips
    hot = int(traffic["hot_head_keys"])
    core = int(round(pass_keys * float(traffic["shared_with_previous"])))
    fresh = pass_keys - hot - core - unseen
    n_passes = int(traffic["distinct_passes"])
    warmup = int(traffic["warmup_passes"])
    window = int(traffic["window_passes"])
    if warmup < 1 or window < 2:
        raise ValueError(f"traffic: warmup_passes {warmup} must be at least "
                         f"1 and window_passes {window} at least 2 (the "
                         f"traced pass is the window's second)")
    if warmup + window > n_passes:
        raise ValueError(f"traffic: warmup_passes {warmup} + window_passes "
                         f"{window} = {warmup + window} passes, more than "
                         f"distinct_passes {n_passes}: a set of files "
                         f"would train twice in one run")
    if min(hot, core, fresh, unseen) < 0:
        raise ValueError("traffic: pass_keys too small for its parts")
    if core + hot + n_passes * fresh > n_resident:
        raise ValueError("traffic: passes need more resident keys than the "
                         "configuration's store holds")
    if n_resident + n_passes * unseen >= 10 ** KEY_DIGITS:
        raise ValueError(f"keys do not fit {KEY_DIGITS} digits")
    if np.gcd(_MULT, n_resident) != 1:
        raise ValueError("resident key count shares a factor with the "
                         "rank multiplier")
    return {
        "n_resident": n_resident, "pass_keys": pass_keys, "hot": hot,
        "core": core, "fresh": fresh, "unseen": unseen,
        "n_passes": n_passes, "warmup_passes": warmup,
        "window_passes": window, "batches": int(traffic["pass_batches"]),
        "lines_per_file": int(config["batch_per_chip"]),
        "files_per_pass": int(traffic["pass_batches"]) * chips,
        "slots": int(model["slots"]), "dense_dim": int(model["dense_dim"]),
        "zipf_a": traffic.get("zipf_a"),
        "label_rate": float(traffic["label_rate"]),
        "label_strength": float(traffic["label_strength"]),
    }


def _resident_key(index: np.ndarray, n_resident: int, seed: int
                  ) -> np.ndarray:
    offset = np.uint64((seed * 7919 + 12345) % n_resident)
    return ((index.astype(np.uint64) * np.uint64(_MULT) + offset)
            % np.uint64(n_resident)) + np.uint64(1)


def keys_of_ranks(p: Dict, seed: int, pass_idx: int, ranks: np.ndarray
                  ) -> np.ndarray:
    """Working-set rank -> feasign, for pass ``pass_idx``."""
    ranks = ranks.astype(np.int64)
    shared = p["hot"] + p["core"]
    index = np.where(ranks < shared, ranks, ranks + pass_idx * p["fresh"])
    keys = _resident_key(index, p["n_resident"], seed)
    first_unseen = p["pass_keys"] - p["unseen"]
    beyond = (p["n_resident"] + 1 + pass_idx * p["unseen"]
              + (ranks - first_unseen))
    return np.where(ranks >= first_unseen, beyond.astype(np.uint64), keys)


def planted_probability(keys: np.ndarray, rate: float, strength: float
                        ) -> np.ndarray:
    """P(label = 1) given slot 0's key: a hash bit of the key is its
    latent +-1 weight (bench.py:_planted_labels)."""
    h = (keys * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(32)
    sign = (h & np.uint64(1)).astype(np.float64) * 2.0 - 1.0
    logit = sign * strength + np.log(rate / (1.0 - rate))
    return 1.0 / (1.0 + np.exp(-logit))


def auc_ceiling(p: Dict, seed: int) -> float:
    """AUC of the scorer that knows every hot key's label probability,
    for slot 0 drawn uniformly from the hot head: what a perfectly
    trained model tends to. Above it means the AUC state holds something
    else than this pass's predictions."""
    hot = keys_of_ranks(p, seed, 0, np.arange(p["hot"]))
    prob = planted_probability(hot, p["label_rate"], p["label_strength"])
    levels, counts = np.unique(prob, return_counts=True)
    share = counts / counts.sum()
    pos = share * levels
    neg = share * (1.0 - levels)
    wins = sum(pos[i] * (neg[:i].sum() + 0.5 * neg[i])
               for i in range(len(levels)))
    return float(wins / (pos.sum() * neg.sum()))


def draw_block(p: Dict, seed: int, pass_idx: int, file_idx: int, n: int
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(ids [n, slots] uint64, labels [n] int, dense [n, dense_dim] int in
    0..9999) for one file: the arrays the text is printed from, and what
    the plain reference is fed."""
    rng = np.random.default_rng([seed, pass_idx, file_idx])
    body = p["pass_keys"] - p["hot"]
    if p["zipf_a"] is None:
        ranks = rng.integers(0, body, (n, p["slots"] - 1))
    else:
        ranks = (rng.zipf(float(p["zipf_a"]), (n, p["slots"] - 1))
                 .astype(np.int64) - 1) % body
    ids = np.empty((n, p["slots"]), np.uint64)
    ids[:, 1:] = keys_of_ranks(p, seed, pass_idx, ranks + p["hot"])
    ids[:, 0] = keys_of_ranks(p, seed, pass_idx,
                              rng.integers(0, p["hot"], n))
    prob = planted_probability(ids[:, 0], p["label_rate"],
                               p["label_strength"])
    labels = (rng.random(n) < prob).astype(np.int64)
    dense = rng.integers(0, 10 ** DENSE_DIGITS, (n, p["dense_dim"]))
    return ids, labels, dense


_THREE = np.array([list(f"{i:03d}".encode()) for i in range(1000)], np.uint8)


def _digits(values: np.ndarray, width: int) -> np.ndarray:
    """[..., width] ASCII digits of non-negative integers, zero padded:
    three digits at a time from a table (a division per digit is several
    times slower)."""
    v = values.astype(np.uint32)
    groups = []
    for _ in range(-(-width // 3)):
        groups.append(_THREE[v % np.uint32(1000)])
        v = v // np.uint32(1000)
    full = np.concatenate(groups[::-1], axis=-1)
    return full[..., full.shape[-1] - width:]


def format_lines(ids: np.ndarray, labels: np.ndarray, dense: np.ndarray
                 ) -> bytes:
    """``<label> s0:<key> ... s25:<key> d:0.dddd,...`` per line: one
    template row repeated, then the digit columns filled in."""
    n, slots = ids.shape
    template, fields = b"0", []
    for j in range(slots):
        template += f" s{j}:".encode()
        fields.append((len(template), KEY_DIGITS))
        template += b"0" * KEY_DIGITS
    for j in range(dense.shape[1]):
        template += b" d:0." if j == 0 else b",0."
        fields.append((len(template), DENSE_DIGITS))
        template += b"0" * DENSE_DIGITS
    template += b"\n"
    out = np.tile(np.frombuffer(template, np.uint8), (n, 1))
    out[:, 0] += labels.astype(np.uint8)
    key_digits = _digits(ids, KEY_DIGITS)
    dense_digits = _digits(dense, DENSE_DIGITS)
    digits = ([key_digits[:, j] for j in range(slots)]
              + [dense_digits[:, j] for j in range(dense.shape[1])])
    for (start, width), d in zip(fields, digits):
        out[:, start:start + width] = d
    return out.tobytes()


def pass_files(out_dir: str, p: Dict, pass_idx: int) -> List[str]:
    return [os.path.join(out_dir, f"pass-{pass_idx:02d}",
                         f"part-{f:05d}")
            for f in range(p["files_per_pass"])]


def write_file(task: Tuple[str, Dict, int, int, int]) -> str:
    """Pool task: one part file. Written under a temporary name and
    renamed, so a killed run leaves no short file under a final name."""
    path, p, seed, pass_idx, file_idx = task
    ids, labels, dense = draw_block(p, seed, pass_idx, file_idx,
                                    p["lines_per_file"])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(format_lines(ids, labels, dense))
    os.replace(tmp, path)
    return path


def tasks(out_dir: str, p: Dict, seed: int) -> List[Tuple]:
    return [(path, p, seed, k, f)
            for k in range(p["n_passes"])
            for f, path in enumerate(pass_files(out_dir, p, k))]
