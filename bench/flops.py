"""Useful FLOPs of one query at one stage, from the configuration's sizes.

A stage runs the query's prompt of ``seq_len`` tokens through the model
once and serves the argmax of the last position.  Useful work is then:

* every weight matrix applied to every prompt position, 2 FLOPs per
  multiply-add (the arithmetic of ``repro.launch.roofline.analytic_costs``
  in prefill mode): ``2 * seq_len * matmul_params``;
* the output head at the last position only: ``2 * d_model * vocab``;
* the sequence mixing over the causal pairs of the prompt, ``S(S+1)/2``
  of them (attention's scores and weighted sum, a recurrent memory in its
  parallel form): ``mixing_flops``.

The stage's reference family (``bench/reference/<family>.py``) gives the
first and the last term for the block kinds it serves; this file names
no kind.  Rows that only pad a partial batch, the other positions'
logits, the masked half of the pairs and the decode cache the prefill
writes are not useful work and are not counted.  Elementwise work (norms,
gates, recurrent cell updates) is not counted either: it is not matrix
work.
"""
from __future__ import annotations

from pathlib import Path

from bench import reference


def stage_flops(stage: dict, bench: Path = reference.BENCH) -> float:
    """Useful FLOPs of one query at ``stage`` (a configuration file's stage
    entry), counted by the family it names in ``bench/reference``."""
    m, s = stage["model"], stage["seq_len"]
    fam = reference.family(stage, bench)
    return (2.0 * s * fam.matmul_params(m)
            + 2.0 * m["d_model"] * m["vocab_size"]
            + fam.mixing_flops(m, s))
