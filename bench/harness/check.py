"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the timed path finished, drawn from the seed and holding the
longest of them, is replayed through the plain float32 reference.  DataMUX
mixes the N lanes of a slot into one stream, so a request's logits depend on
every token fed to its slot since the slot was last reset: the reference
runs each such epoch of the slot's stream whole, from the recorded inputs of
the engine's steps.  Before that, each sampled request's own lane is held to
the traffic: it must have been fed exactly its prompt and then its own
generated tokens, one per step, at consecutive positions.

The number compared is the widest gap by which a served token's reference
logit lies below the reference's best logit at that position (greedy
traffic: the program served its own argmax).  The control puts the
reference, computed with every matmul operand rounded to float8_e4m3fn, in
the program's place, and reads the same gap for the token it puts first.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from harness import drive

TARGET_TOKENS = 256      # served tokens in the sample, at least
MAX_EPOCHS = 8           # slot epochs replayed (one reference batch)
T_BUCKET = 256           # epoch length padded to a multiple of this


@dataclasses.dataclass
class Sample:
    tokens: np.ndarray       # (E, T, N)
    mask: np.ndarray         # (E, T, N)
    queries: np.ndarray      # (M, 3) epoch, step, lane
    served: np.ndarray       # (M,) the token the program served there
    rids: list
    feed_faults: int         # sampled lanes not fed as the traffic says


def _lanes(served: drive.Served) -> dict:
    """rid -> (slot, lane, [input indices]) over the recorded steps."""
    out: dict = {}
    for k, inp in enumerate(served.inputs):
        for s, l in zip(*np.nonzero(inp.grid >= 0)):
            rid = int(inp.grid[s, l])
            ent = out.setdefault(rid, (int(s), int(l), []))
            ent[2].append(k)
    return out


def _epoch_start(served: drive.Served, slot: int, k: int) -> int:
    p = served.prefix_len
    while k > 0 and served.inputs[k].pos[slot] != p:
        k -= 1
    return k


def choose(served: drive.Served, seed: int) -> list:
    """Finished requests to replay: the longest, then others drawn from the
    seed, until TARGET_TOKENS are served or MAX_EPOCHS slot epochs used."""
    lanes = _lanes(served)
    t0, t1 = served.window
    done = [r for r in served.requests.values()
            if r.done and r.rid in lanes and served.tok_times.get(r.rid)
            and served.tok_times[r.rid][-1] > t0]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    longest = max(done, key=lambda r: (len(r.output), -r.rid))
    order = [longest] + [done[i] for i in rng.permutation(len(done))
                         if done[i] is not longest]
    picked, epochs, tokens = [], set(), 0
    for r in order:
        s, _, ks = lanes[r.rid]
        ep = (s, _epoch_start(served, s, ks[0]))
        if ep not in epochs and len(epochs) == MAX_EPOCHS:
            continue
        epochs.add(ep)
        picked.append(r)
        tokens += len(r.output)
        if tokens >= TARGET_TOKENS:
            break
    return picked


def sample(served: drive.Served, picked: list) -> Sample:
    lanes = _lanes(served)
    n = served.inputs[0].grid.shape[1]
    p = served.prefix_len
    epochs: dict = {}            # (slot, start) -> last input index needed
    rows = []                    # (epoch key, input index, lane, token)
    faults = 0
    for r in picked:
        s, l, ks = lanes[r.rid]
        want = list(r.prompt) + list(r.output[:-1])
        fed = [int(served.inputs[k].tokens[s, l]) for k in ks]
        live = all(served.inputs[k].mask[s, l] for k in ks)
        steady = ks == list(range(ks[0], ks[0] + len(ks)))
        if fed != [int(t) for t in want] or not live or not steady:
            faults += 1
            continue
        start = _epoch_start(served, s, ks[0])
        for j, k in enumerate(range(start, ks[-1] + 1)):
            if served.inputs[k].pos[s] != p + j:
                faults += 1
                break
        else:
            key = (s, start)
            epochs[key] = max(epochs.get(key, 0), ks[-1])
            first = ks[0] + len(r.prompt) - 1
            for j, tok in enumerate(r.output):
                rows.append((key, first + j, l, int(tok)))
    keys = sorted(epochs)
    t_len = max([epochs[k] - k[1] + 1 for k in keys] or [1])
    t_len = -(-t_len // T_BUCKET) * T_BUCKET
    e_len = MAX_EPOCHS
    tokens = np.zeros((e_len, t_len, n), np.int32)
    mask = np.zeros((e_len, t_len, n), bool)
    for e, (s, start) in enumerate(keys):
        for j, k in enumerate(range(start, epochs[(s, start)] + 1)):
            tokens[e, j] = served.inputs[k].tokens[s]
            mask[e, j] = served.inputs[k].mask[s]
    index = {k: e for e, k in enumerate(keys)}
    queries = np.array([(index[key], k - key[1], l) for key, k, l, _ in rows],
                       np.int32).reshape(-1, 3)
    toks = np.array([t for *_, t in rows], np.int64)
    return Sample(tokens=tokens, mask=mask, queries=queries, served=toks,
                  rids=[r.rid for r in picked], feed_faults=faults)


def reference(config: dict):
    return importlib.import_module(f"references.{config['reference']}")


def gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the best."""
    return ref.max(-1) - ref[np.arange(len(chosen)), chosen]


def readings(config: dict, cfg, seed: int, smp: Sample, *,
             control: bool = False) -> dict:
    """The program's widest gap (and, with ``control``, the control's)."""
    import jax.numpy as jnp
    from harness.weights import Weights
    ref_mod = reference(config)
    w = Weights(cfg, seed)
    model = dict(config["model"])
    if not len(smp.queries):
        return {"logit_gap": float("inf"), "tokens": 0}
    ref = ref_mod.logits(model, config["mux"], w, smp.tokens, smp.mask,
                         smp.queries)
    out = {"logit_gap": float(gaps(ref, smp.served).max()),
           "tokens": int(len(smp.served)),
           "argmax_share": float(np.mean(ref.argmax(-1) == smp.served))}
    if control:
        ctl = ref_mod.logits(model, config["mux"], w, smp.tokens, smp.mask,
                             smp.queries, quant=jnp.float8_e4m3fn)
        out["control_gap"] = float(gaps(ref, ctl.argmax(-1)).max())
    return out


def judge(config: dict, cfg, seed: int, served: drive.Served, *,
          control: bool = False) -> dict:
    """The numbers compared, each with its limit, and ``correct``.

    Returns ``{"program": (correct, compared), "tokens", "requests",
    "argmax_share"}``; with ``control``, also ``"control": (correct,
    compared)``, the same comparison with the control's tokens in the
    served tokens' place at the same prompts and positions."""
    picked = choose(served, seed)
    smp = sample(served, picked)
    got = readings(config, cfg, seed, smp, control=control)
    limit = config["check"]["max_logit_gap"]

    def verdict(gap: float) -> tuple:
        compared = {
            "logit_gap": {"value": gap, "limit": limit},
            "feed_faults": {"value": smp.feed_faults, "limit": 0},
            "refused": {"value": served.refused, "limit": 0},
        }
        return bool(got["tokens"] > 0 and all(
            c["value"] <= c["limit"] for c in compared.values())), compared

    out = {"program": verdict(got["logit_gap"]), "tokens": got["tokens"],
           "requests": len(picked),
           "argmax_share": got.get("argmax_share", 0.0)}
    if control:
        out["control"] = verdict(got.get("control_gap", float("inf")))
    return out
