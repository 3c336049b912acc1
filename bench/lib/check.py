"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and holding the longest
one, is replayed through the plain reference: one pass over each prompt
with its served tokens.  For every served token the reference gives the
gap by which that token's logit lies below its best logit, in units of
the root mean square of that position's logits.  The number compared is
the widest such gap.  Greedy tokens only: the traffic here is greedy.

The control puts the reference, computed a precision lower, in the
program's place: at each position it reads the gap of the token that the
lower precision puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def sample(records: Sequence[Any], seed: int, min_tokens: int,
           max_requests: int) -> List[Any]:
    """The longest finished request, then others in an order the seed
    draws, until ``min_tokens`` served tokens or ``max_requests``."""
    done = [r for r in records if r.ok and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


@jax.jit
def _stats(lg, targets):
    """Per position: best logit, RMS, logit of ``targets``, argmax."""
    best = jnp.max(lg, axis=-1)
    rms = jnp.sqrt(jnp.mean(lg * lg, axis=-1))
    at = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return best, rms, at, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def replay(ref, cfg: Dict[str, Any], weights, prompt: np.ndarray,
           served: Sequence[int], length: int, control: bool = False
           ) -> Dict[str, np.ndarray]:
    """Reference pass over ``prompt + served[:-1]`` padded to ``length``
    (one program for every request; causal, so padding at the end leaves
    the positions that count alone).  Returns the served tokens' gaps,
    and with ``control`` the gaps of the control's own first choices."""
    n, m = len(prompt), len(served)
    seq = np.zeros((length,), np.int32)
    seq[:n] = prompt
    seq[n:n + m - 1] = np.asarray(served[:-1], np.int32)
    targets = np.zeros((length,), np.int32)
    targets[n - 1:n - 1 + m] = np.asarray(served, np.int32)
    rows = slice(n - 1, n - 1 + m)
    lg = ref.logits(cfg, weights, seq)
    best, rms, at, _ = (np.asarray(a) for a in _stats(lg, jnp.asarray(targets)))
    out = {"served": ((best - at) / rms)[rows]}
    if control:
        low = ref.logits(cfg, weights, seq, mode="fp8")
        pick = np.asarray(_stats(low, jnp.asarray(targets))[3])
        del low
        _, _, at_c, _ = (np.asarray(a) for a in
                         _stats(lg, jnp.asarray(pick)))
        out["control"] = ((best - at_c) / rms)[rows]
    del lg
    return out
