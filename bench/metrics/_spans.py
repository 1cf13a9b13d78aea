"""Pairing of the window's client calls with the engine's spans."""

from __future__ import annotations

from typing import Dict, List, Tuple


def by_name(run, name: str) -> List[dict]:
    return [s for s in run.spans if s["name"] == name]


def decision_spans(run) -> List[Tuple[dict, dict, dict]]:
    """(client decision, ``rpc.suggest_batch`` span, ``service.suggest_batch``
    span) for each decision of the window whose spans lie inside its call."""
    spans: Dict[int, dict] = {s["span_id"]: s for s in run.spans}
    service = {}
    for s in by_name(run, "service.suggest_batch"):
        parent = spans.get(s["parent_id"])
        if parent is not None and parent["name"] == "rpc.suggest_batch":
            service.setdefault(s["attrs"].get("job"), []).append((s, parent))
    out = []
    for dec in run.decisions:
        if "error" in dec:
            continue
        for s, rpc in service.get(dec.get("job"), ()):
            if dec["t0"] <= rpc["t0"] and rpc["t1"] <= dec["t1"]:
                out.append((dec, rpc, s))
                break
    return out
