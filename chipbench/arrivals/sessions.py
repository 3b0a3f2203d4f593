"""Multi-turn conversations over shared system prompts.

Each app has one system prompt; a conversation picks an app and sends
turns whose prompts grow: turn k+1's prompt is turn k's prompt, then
turn k's assistant reply, then a new user message.  So a prefix cache
can reuse the app's system prompt on a conversation's first turn and
the previous prompt on every later one.

The assistant replies in the history are synthetic: seed-drawn ids as
long as the previous turn's output, not the tokens the system served.
The schedule is therefore open loop and fixed, the same whatever the
system serves and however fast.

Keys of the mix:

* ``sessions_rate``: conversations start open loop, Poisson:
  ``round(sessions_rate * seconds)`` starts whose exponential gaps are
  scaled to end inside the window;
* ``apps``: each app's popularity, the chance a conversation picks it;
  ``system_tokens``: the length of every app's system prompt;
* ``turns``: ``mean`` and ``max``: a conversation's number of turns is
  geometric with ``p = 1 / mean``, capped at ``max``;
* ``think_s``: turn k+1 is due ``min`` plus an exponential of mean
  ``mean_extra`` seconds after turn k was due;
* ``user`` / ``output``: lognormal ``mean`` and ``std``, clipped to
  ``[lo, hi]``: a user message's tokens, and the tokens served to a
  turn (the prefill's first token included);
* ``max_len``: a conversation ends before the first turn whose prompt
  and output together would pass it;
* ``shape_seed``: draws the due times, lengths and app choices.

The run's ``--seed`` draws the ids: the system prompts, the user
messages and the synthetic replies.  Turns due after the window closes
are not sent.
"""
from __future__ import annotations

from typing import List

import numpy as np

from traffic import Item, lognormal


def schedule(mix: dict, seconds: float) -> list:
    """Per conversation ``(app, turns)``, each turn ``(due_s, user
    tokens, output tokens)``, from the mix's own ``shape_seed``: the
    same for every run seed.  Every conversation draws the same numbers
    whatever is cut, so one change of length moves nothing else."""
    n = max(1, int(round(float(mix["sessions_rate"]) * seconds)))
    rng = np.random.default_rng(int(mix["shape_seed"]))
    share = np.asarray(mix["apps"], float)
    app = rng.choice(len(share), n, p=share / share.sum())
    t_max = int(mix["turns"]["max"])
    turns = np.minimum(rng.geometric(1.0 / float(mix["turns"]["mean"]), n),
                       t_max)
    user = lognormal(rng, mix["user"], n * t_max).reshape(n, t_max)
    output = lognormal(rng, mix["output"], n * t_max).reshape(n, t_max)
    think = (float(mix["think_s"]["min"])
             + rng.exponential(float(mix["think_s"]["mean_extra"]),
                               (n, t_max)))
    gaps = rng.exponential(1.0, n)
    # the n-th start lands at seconds * n / (n + 1), as in ``poisson``
    gaps *= seconds * n / (n + 1) / gaps.sum()
    start = np.cumsum(gaps)
    system, max_len = int(mix["system_tokens"]), int(mix["max_len"])
    out = []
    for i in range(n):
        due, prompt, conv = float(start[i]), system, []
        for k in range(int(turns[i])):
            if k:
                due += float(think[i, k])
                prompt += int(output[i, k - 1])
            prompt += int(user[i, k])
            if prompt + int(output[i, k]) > max_len or due >= seconds:
                break
            conv.append((due, int(user[i, k]), int(output[i, k])))
        out.append((int(app[i]), conv))
    return out


def generate(mix: dict, seconds: float, seed: int, vocab: int) -> List[Item]:
    """The run's requests, in due order: each turn's prompt is the
    conversation's history so far and a new user message."""
    convs = schedule(mix, seconds)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    ids = lambda k: rng.integers(0, vocab, k, dtype=np.int32)
    systems = [ids(int(mix["system_tokens"])) for _ in mix["apps"]]
    turns = []
    for c, (app, conv) in enumerate(convs):
        history = systems[app]
        for k, (due, user, output) in enumerate(conv):
            prompt = np.concatenate([history, ids(user)])
            turns.append((due, c, k, prompt, output))
            history = np.concatenate([prompt, ids(output)])
    turns.sort(key=lambda x: x[:3])
    return [Item(rid, due, prompt, output)
            for rid, (due, _, _, prompt, output) in enumerate(turns)]


def warmup_prompts(mix: dict, page_size: int, seed: int,
                   vocab: int) -> List[np.ndarray]:
    """One seeded prompt per page count a turn can hand off, longest
    first, all starting with one system prompt.  After the first, the
    prefix cache hits that system prompt and the rest compute suffixes
    of every length bucket a turn computes in the window; the first
    ones, before the cache holds it, compile the whole-prompt bucket of
    a miss."""
    system = int(mix["system_tokens"])
    lo = -(-(system + int(mix["user"]["lo"])) // page_size)
    top = int(mix["max_len"]) - int(mix["output"]["lo"])
    hi = -(-top // page_size)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    head = rng.integers(0, vocab, system, dtype=np.int32)
    return [np.concatenate([head, rng.integers(0, vocab,
                                               min(p * page_size, top)
                                               - system, dtype=np.int32)])
            for p in range(hi, lo - 1, -1)]
