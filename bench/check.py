"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished (drawn
from the seed, the longest always in it) is run through the configuration's
plain reference (``bench/references/<reference>.py``) over its prompt and
the tokens the engine served.  For every served token the reference gives
its gap: how far that token's logit lies below the reference's best.  The
cell's limits (``bench/limits/<cell>.json``) name the numbers compared:

* ``max_logit_gap``: the widest gap of any served token;
* ``mean_logit_gap``: the mean gap over every served token;
* ``mean_logit_gap_decode``: the mean gap over the tokens served after each
  request's first, at the positions the model decodes (PERF.md says why a
  cell compares this one);
* ``rms_logit_gap_decode``: the root mean square of those gaps, which
  weighs the wider ones more.
"""
from __future__ import annotations

import importlib

import numpy as np

NUMBERS = {
    "max_logit_gap": lambda gaps: max(g.max() for g in gaps),
    "mean_logit_gap": lambda gaps: np.concatenate(gaps).mean(),
    "mean_logit_gap_decode": lambda gaps: np.concatenate(
        [g[1:] for g in gaps]).mean(),
    "rms_logit_gap_decode": lambda gaps: np.sqrt(np.square(np.concatenate(
        [g[1:] for g in gaps])).mean()),
}


def sample(tracks, seed: int, n: int):
    """The finished requests to compare: the longest, then ``n - 1``
    others drawn from the seed."""
    done = [t for t in tracks if t.state == "done" and t.n_out == t.gen.max_new]
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.gen.prompt) + t.n_out, -t.rid))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([3, seed & 0xFFFFFFFF, seed >> 32])
    picks = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(picks)]


def reference(conf: dict):
    return importlib.import_module(f"bench.references.{conf['reference']}")


def served_gaps(conf: dict, seed: int, served, control: bool = False):
    """Per sampled request, the gaps of its served tokens (``served``:
    (prompt, output tokens) pairs); with ``control``, also those of the
    tokens the reference computed one precision step down puts first at
    the same positions."""
    mod = reference(conf)
    ref = mod.Reference(conf, seed)
    prog = [mod.gaps(ref, p, o) for p, o in served]
    if not control:
        return prog
    return prog, [mod.gaps(ref, p, o, control=True) for p, o in served]


def numbers(gaps, names) -> dict:
    """Each named number of the per-request gaps; with nothing to compare
    a number reads infinite, and fails."""
    out = {}
    for name in names:
        try:
            v = float(NUMBERS[name](gaps)) if gaps else np.inf
        except ValueError:              # no token at those positions
            v = np.inf
        out[name] = v if np.isfinite(v) else np.inf
    return out


def compare(conf: dict, seed: int, served, limits: dict,
            control: bool = False):
    """(the numbers compared, each beside its limit; tokens compared; with
    ``control``, every number of the program and of the control)."""
    gaps, ctl = [], None
    if served and control:
        gaps, ctl_gaps = served_gaps(conf, seed, served, control=True)
        ctl = {"program": numbers(gaps, NUMBERS),
               "control": numbers(ctl_gaps, NUMBERS)}
    elif served:
        gaps = served_gaps(conf, seed, served)
    got = numbers(gaps, limits)
    checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    return checks, sum(len(g) for g in gaps), ctl


def passed(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
