"""What decides ``correct``: served tokens against the plain reference.

After the window has closed and the engine is gone, two samples of the
window's requests are drawn from the seed:

* whole requests, finished or still decoding at the close (their served
  tokens are what the user has already received), the longest always in
  it: the reference runs once over each prompt followed by its served
  tokens;
* first tokens only, of further requests that got one in the window:
  the reference runs over the prompt alone.

At every served position the reference's float32 logits say how far the
served token's logit lies below the best one. The first token comes from
the prefill, which reads no frozen page; every later one from a decode
step over the paged, partly 4-bit cache. The cell file names which
statistics of these gaps are compared (``compare``) and their limits, set
between what sound runs read and what the lower-precision control reads.
The decode statistic is taken request by request, the worst request
counting, so that a fault confined to a few slots is not averaged away.

The control (``control=True``) is the reference put in the program's
place one precision lower than the configuration states: matmul operands
in float8 for its bfloat16, and K and V rounded to 8-value codebooks per
page for its 16-value (4-bit) ones (``references/*``, precision
``CONTROL``). At the same positions it reads the gap of the token that
the control's forward puts first, and goes through the same ``compare``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256          # logit rows per head call
CONTROL = "fp8_kv3"  # the reference's precision for the control


def sample(items, outputs: dict, *, seed: int, min_tokens: int,
           max_requests: int, first_requests: int = 0) -> list:
    """(item, served tokens) pairs to check. Whole requests: the longest
    (prompt + served) first, then others in the seed's order until
    ``min_tokens`` served tokens or ``max_requests`` requests. Then up to
    ``first_requests`` more, in the seed's order, each with its first
    token only."""
    done = [(it, list(outputs[it.id])) for it in items
            if len(outputs.get(it.id, ())) >= 1]
    if not done:
        return []
    whole = [d for d in done if len(d[1]) >= 2] or done
    longest = max(whole, key=lambda d: (len(d[0].prompt) + len(d[1]),
                                        d[0].id))
    rest = [d for d in done if d is not longest]
    order = np.random.default_rng(int(seed) ^ 0xC0FFEE).permutation(len(rest))
    picked, n, firsts = [longest], len(longest[1]), []
    for i in order:
        it, served = rest[i]
        if n < min_tokens and len(picked) < max_requests:
            picked.append((it, served))
            n += len(served)
        elif len(firsts) < first_requests:
            firsts.append((it, served[:1]))
    return picked + firsts


@jax.jit
def _served_gap(z, tok):
    chosen = jnp.take_along_axis(z, tok[:, None], axis=1)[:, 0]
    return jnp.max(z, axis=1) - chosen


@jax.jit
def _control_gap(z, zc):
    c = jnp.argmax(zc, axis=1)
    return jnp.max(z, axis=1) - jnp.take_along_axis(z, c[:, None], 1)[:, 0]


def _bucket(n: int) -> int:
    """Padded sequence length of a reference batch: a multiple of 128."""
    return -(-n // 128) * 128


def gaps(ref, weights, conf: dict, picked: list, *, control: bool = False,
         batch_tokens: int = 4096) -> list[dict]:
    """For each (item, served) in ``picked``, per served token: ``served``
    = how far its reference logit lies below the reference's best; with
    ``control``, ``control`` = the same for the token the control's
    forward ranks first. Sequences of about one length run through the reference
    a few at a time (``batch_tokens`` positions a batch, always as many
    rows, so that each length is one program)."""
    seqs = [list(it.prompt) + list(served[:-1]) for it, served in picked]
    by_len = sorted(range(len(picked)), key=lambda i: len(seqs[i]))
    out: list = [None] * len(picked)
    groups, cur = [], []
    for i in by_len:
        S = _bucket(len(seqs[i]))
        if cur and (S != cur[0][0] or len(cur) >= max(1, batch_tokens // S)):
            groups.append(cur)
            cur = []
        cur.append((S, i))
    if cur:
        groups.append(cur)
    for group in groups:
        S = group[0][0]
        toks = np.zeros((max(1, batch_tokens // S), S), np.int32)
        for r, (_, i) in enumerate(group):
            toks[r, :len(seqs[i])] = seqs[i]
        x = ref.hidden_states(weights, conf, toks)
        xc = (ref.hidden_states(weights, conf, toks, precision=CONTROL)
              if control else None)
        for r, (_, i) in enumerate(group):
            it, served = picked[i]
            P, n = len(it.prompt), len(served)
            res = {"served": [], "control": []}
            for a in range(0, n, ROWS):
                rows = np.minimum(np.arange(P - 1 + a, P - 1 + a + ROWS),
                                  P - 2 + n)
                tok = np.zeros(ROWS, np.int32)
                m = min(ROWS, n - a)
                tok[:m] = served[a:a + m]
                z = ref.logits(weights, conf, x[r, rows])
                res["served"].append(
                    np.asarray(_served_gap(z, jnp.asarray(tok)))[:m])
                if control:
                    zc = ref.logits(weights, conf, xc[r, rows],
                                    precision=CONTROL)
                    res["control"].append(np.asarray(_control_gap(z, zc))[:m])
            out[i] = {k: np.concatenate(v) if v else np.zeros(0)
                      for k, v in res.items()}
    return out


def summarize(per_request: list, *, min_decode: int = 1) -> dict:
    """Over all checked tokens, the first tokens (from prefill) and the
    decoded ones: widest and mean gap, and the share of tokens that are
    not the reference's first choice. For decode also the worst request:
    the largest mean decode gap of any request with at least
    ``min_decode`` decoded tokens checked."""
    def stats(g):
        if not len(g):
            return {"tokens": 0}
        return {"tokens": int(len(g)), "widest_gap": float(g.max()),
                "mean_gap": float(g.mean()),
                "off_top_share": float((g > 0).mean())}

    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0))
    out = stats(cat(per_request))
    out["first"] = stats(cat([g[:1] for g in per_request]))
    out["decode"] = stats(cat([g[1:] for g in per_request]))
    means = [float(g[1:].mean()) for g in per_request
             if len(g) - 1 >= max(1, min_decode)]
    out["decode"]["requests"] = len(means)
    if means:
        out["decode"]["worst_request_mean_gap"] = max(means)
    return out


def compare(summary: dict, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all hold: at
    least one token checked, and each ``part.stat`` of ``limits`` (e.g.
    ``decode.worst_request_mean_gap``) at most its limit. A number that
    could not be read fails."""
    compared = {"tokens_checked": {"value": summary["tokens"], "limit": 1,
                                   "rule": ">="}}
    for key, limit in limits.items():
        part, stat = key.split(".")
        compared[key.replace(".", "_")] = {
            "value": summary[part].get(stat), "limit": limit, "rule": "<="}
    correct = all(v["value"] is not None and (
        v["value"] >= v["limit"] if v["rule"] == ">=" else
        v["value"] <= v["limit"]) for v in compared.values())
    return compared, correct
