"""Stage step, whole-step share of the chip's bf16 peak, in percent: the
useful FLOPs (each stage's ``flops`` in the record, counted at cell load by
``bench/flops.py``) of the step programs' executions in the profiler trace
over their device time in that trace times the peak.

A stage's step takes its prompts as ``s32[batch, seq_len]``, so a step
program whose operations touch that shape, and no other stage's, serves
that stage; each of its executions does ``batch`` rows of that stage's
work.  Rows that only pad a partial batch are not useful work: every
execution's rows are scaled by the window's batch fill, ``completed /
(ServeStats.batches x batch)``, since each batch passes every stage of the
chain whole.  A step program that serves no stage, or more than one, is
left out of both sides.  ``mfu_pct.over`` reads the same number in the
over-capacity cell, where it moves ``served_qps``.
"""


def read(record):
    tr = record["trace"]
    if not tr or not record["batches"]:
        return None
    fill = record["completed"] / (record["batches"] * record["batch"])
    by_len = {}
    for st in record["stages"]:
        by_len.setdefault(st["seq_len"], []).append(st)
    flops = seconds = 0.0
    for prog in tr["programs"].values():
        stages = {id(st): (b, st) for b, s in prog["shapes"]
                  for st in by_len.get(s, [])}
        if len(stages) != 1:
            continue
        ((rows, st),) = stages.values()
        flops += prog["count"] * rows * fill * st["flops"]
        seconds += prog["seconds"]
    if not seconds:
        return None
    return 100.0 * flops / (seconds * record["peaks"]["bf16_flops"])
