"""Candidate pairs per completed query that the residual condition of a left
semi or left anti join (a decorrelated ``[NOT] EXISTS``) was evaluated on:
the engine's ``joinConditionPairs`` (``ops/join.py``; a left row paired with
every build row of its key, read back once a stream chunk from the count
program). A program without the counter (before PR 34), or a cell whose
joins carry no such condition, reports nothing."""


def read(window):
    if "joinConditionPairs" not in window.counters:
        return None
    return window.per_query("joinConditionPairs")
