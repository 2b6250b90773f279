"""Whether what the timed window served is right: the decision behind a
run's ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests it served, drawn from the seed and always holding the request
with the most served tokens, goes through the plain float32 reference that
the configuration file names (``bench/reference/<name>.py``,
``load_reference``) with its prompt and the tokens the window served.
At every served position the reference's best logit and its logit of the
served token are read.  Greedy decoding serves the argmax, so a right
program misses the reference's best only by rounding, while a wrong cache
row, a skipped layer, an exit that fired or a changed token lands far
below it.  Two numbers come of it:

* ``logit_gap``: the widest gap between the two, over every compared
  position (one altered token shows here);
* ``logit_gap_mean``: their mean gap (a lower precision throughout shows
  here: it misses more often, and by more).

The exit probes are checked on the program's own compiled probe stages,
the programs the window's decode steps ran, at the pool's batch: rows of
the reference's residual stream after each exit layer, at positions drawn
from the compared ones, go into the probe, and the entropy at which each
row's exit decision flips is found by bisection on the probe's threshold
(the probe returns only its decisions).  ``exit_entropy_gap`` is the mean
gap in nats between those entropies and the reference's.  The probes must
not compile anew for this (``exit_probe_programs_added``).

Finished requests are sampled first; where too few finished, requests
still in flight top the sample up with the tokens they had shown.  Every
finished request must also have served exactly ``max_new`` tokens, all
inside the vocabulary (random weights never emit an end token).
"""
from __future__ import annotations

import importlib
import os
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

EXIT_ROWS = 64          # rows of a probe call that are bisected
BISECT_STEPS = 24       # normalized entropy to 2 / 2**24


def load_reference(config: Dict):
    """The module ``bench/reference/<name>.py`` that the configuration
    file names under ``"reference"`` (``dense`` where it names none)."""
    name = config.get("reference", "dense")
    if "/" in name or not os.path.isfile(
            os.path.join(HERE, "reference", name + ".py")):
        raise SystemExit(f"bench: no reference module "
                         f"bench/reference/{name}.py")
    return importlib.import_module("reference." + name)


def sample(log, seed: int, k: int) -> List[int]:
    """Up to k request ids: the one with the most served tokens, then
    others drawn from the seed, finished ones before those in flight."""
    shown = {i: sum(t <= log.end for t in v) for i, v in log.visible.items()}
    done = sorted(i for i, r in log.requests.items()
                  if r.done and shown[i] == len(r.out_tokens) > 0)
    live = sorted(set(i for i in shown if shown[i] > 0) - set(done))
    pool = done or live
    if not pool:
        return []
    first = max(pool, key=lambda i: (shown[i], -i))
    rng = np.random.default_rng([seed, 0x5EED])
    rest = [int(i) for ids in (done, live)
            for i in rng.permutation([i for i in ids if i != first])]
    return [first] + rest[:k - 1]


def batch(log, ids: List[int], s: int, t: int
          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Padded tokens [s, t] (prompt + served tokens), the served token
    each position should predict (``look``) and a mask of the compared
    positions."""
    tokens = np.zeros((s, t), np.int32)
    look = np.zeros((s, t), np.int32)
    mask = np.zeros((s, t), bool)
    for row, i in enumerate(ids):
        r = log.requests[i]
        n = len([x for x in log.visible[i] if x <= log.end])
        out = np.asarray(r.out_tokens[:n], np.int32)
        seq = np.concatenate([np.asarray(r.tokens, np.int32), out])[:t]
        tokens[row, :seq.size] = seq
        p0 = r.tokens.size - 1
        look[row, p0:p0 + n] = out
        mask[row, p0:p0 + n] = True
    return tokens, look, mask


def exit_rows(mask, seed: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """n compared positions (row, column) drawn from the seed."""
    rs, ts = np.nonzero(mask)
    pick = np.random.default_rng([seed, 0xE817]).choice(
        rs.size, size=min(n, rs.size), replace=False)
    return rs[pick], ts[pick]


def reference_pass(ref, model: Dict, tokens, look, mask,
                   seed: int, n_rows: int) -> Dict:
    """The reference's side of every number: its best logit and its logit
    of the served token at every position, its final residual stream,
    and, per exit head, the sampled rows of the stream after the exit's
    layer and the head's entropy there."""
    layers = list(model["exit_layers"])
    x, taps = ref.hidden(tokens, taps=layers) if layers \
        else (ref.hidden(tokens), {})
    best, at, _ = ref.head(x, look)
    rows = exit_rows(mask, seed, n_rows)
    exits = [np.asarray(taps[n])[rows] for n in layers]
    return {"x": x, "best": best, "at": at, "exits": exits,
            "entropy": [ref.exit_entropy(xe, e)
                        for e, xe in enumerate(exits)]}


def logit_numbers(best, at, mask) -> Dict[str, float]:
    g = (best - at)[mask]
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean())}


def entropy_gap(got: List[np.ndarray], want: List[np.ndarray]) -> float:
    """Mean gap in nats over every probed row of every exit head."""
    if not want:
        return 0.0
    return float(np.mean(np.abs(np.concatenate(got)
                                - np.concatenate(want))))


def probe_entropy(stage, params, xs: np.ndarray, vocab: int) -> np.ndarray:
    """The entropy in nats that the compiled probe ``stage`` (a
    ``StageSpec`` of ``audit_stages()``) computes for rows xs [n, D].

    The probe marks a row as exited where its entropy over log(vocab)
    falls under the threshold it is given; one call answers for every row
    at once, so the rows are bisected together, one call per distinct
    midpoint."""
    _, x_s, alive_s, first_s, thr_s = stage.args
    b, d = x_s.shape[0], x_s.shape[-1]
    n = xs.shape[0]
    x = jnp.asarray(np.resize(np.asarray(xs, np.float32), (b, d))
                    .reshape(x_s.shape), x_s.dtype)
    alive = jnp.ones(alive_s.shape, alive_s.dtype)
    first = jnp.zeros(first_s.shape, first_s.dtype)
    lo = np.zeros(n, np.float32)
    hi = np.full(n, 2.0, np.float32)
    for _ in range(BISECT_STEPS):
        mid = ((lo.astype(np.float64) + hi) / 2).astype(np.float32)
        for t in np.unique(mid):
            still, _ = stage.fn(params, x, alive, first,
                                jnp.asarray(t, thr_s.dtype))
            under = ~np.asarray(still)[:n]
            at = mid == t
            hi = np.where(at & under, t, hi)
            lo = np.where(at & ~under, t, lo)
    return (lo.astype(np.float64) + hi) / 2 * np.log(float(vocab))


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {value, limit}} for every number; counts have the limit 0."""
    return {k: {"value": v, "limit": limits.get(k, 0)}
            for k, v in numbers.items()}


def correct(checks: Dict) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def select(model: Dict, log, traffic: Dict, seed: int, t: int) -> Dict:
    """The sampled requests as one padded batch (``batch``), and how many
    finished requests served a wrong number of tokens or a token outside
    the vocabulary."""
    vocab = model["vocab_size"]
    wrong = sum(1 for r in log.requests.values()
                if r.done and (len(r.out_tokens) != r.max_new
                               or any(not 0 <= x < vocab
                                      for x in r.out_tokens)))
    ids = sample(log, seed, traffic["check_requests"])
    tokens, look, mask = batch(log, ids, traffic["check_requests"], t)
    return {"ids": ids, "tokens": tokens, "look": look, "mask": mask,
            "wrong_lengths": wrong}


def program_numbers(reference, model: Dict, params, sel: Dict, seed: int,
                    probes: List) -> Tuple[Dict, Dict]:
    """The program's numbers, and the reference pass behind them;
    ``reference`` is the module of ``load_reference``."""
    n_rows = min([EXIT_ROWS] + [p.args[1].shape[0] for p in probes])
    rp = reference_pass(reference.Reference(model, params), model,
                        sel["tokens"], sel["look"], sel["mask"], seed,
                        n_rows)
    nums = logit_numbers(rp["best"], rp["at"], sel["mask"])
    nums["exit_entropy_gap"] = entropy_gap(
        [probe_entropy(p, params, xe, model["vocab_size"])
         for p, xe in zip(probes, rp["exits"])], rp["entropy"])
    return nums, rp


def control_numbers(reference, model: Dict, params, sel: Dict, rp: Dict,
                    quant: str) -> Dict:
    """The same numbers for the reference computed in a lower precision
    and put in the program's place: at the same prompts and served
    tokens, the token it puts first at each position, and its exit-head
    entropies on the same rows."""
    rq = reference.Reference(model, params, quant)
    _, _, arg = rq.head(rq.hidden(sel["tokens"]), sel["look"])
    _, at, _ = reference.Reference(model, params).head(rp["x"], arg)
    nums = logit_numbers(rp["best"], at, sel["mask"])
    nums["exit_entropy_gap"] = entropy_gap(
        [rq.exit_entropy(xe, e) for e, xe in enumerate(rp["exits"])],
        rp["entropy"])
    return nums


def compare(reference, model: Dict, params, log, traffic: Dict,
            cell: Dict, seed: int, t: int, probes: List):
    """The checks of one run, {name: {value, limit}}, and how many
    requests failed them.  ``reference`` is the module of
    ``load_reference``; ``probes`` are the probe stages of
    ``audit_stages()``, one per exit head in order."""
    sel = select(model, log, traffic, seed, t)
    ids, mask, limits = sel["ids"], sel["mask"], cell["limits"]
    sizes = sum(p.fn._cache_size() for p in probes)
    nums = {"logit_gap": 0.0, "logit_gap_mean": 0.0,
            "exit_entropy_gap": 0.0}
    failed = 0
    if ids:     # else no_tokens_compared fails
        nums, rp = program_numbers(reference, model, params, sel, seed,
                                   probes)
        g = np.where(mask, rp["best"] - rp["at"], 0.0)[:len(ids)]
        n = np.maximum(mask[:len(ids)].sum(1), 1)
        failed = int(np.sum((g.max(1) > limits["logit_gap"])
                            | (g.sum(1) / n > limits["logit_gap_mean"])))
        if nums["exit_entropy_gap"] > limits["exit_entropy_gap"]:
            failed = len(ids)
    nums["exit_probe_programs_added"] = sum(
        p.fn._cache_size() for p in probes) - sizes
    nums["no_tokens_compared"] = int(mask.sum() == 0)
    nums["wrong_lengths"] = sel["wrong_lengths"]
    print(f"compared {int(mask.sum())} served tokens of {len(ids)} "
          f"requests {ids}, and the exit probes on "
          f"{[len(e) for e in rp['exits']] if ids else []} rows")
    return judge(nums, limits), failed + sel["wrong_lengths"]
