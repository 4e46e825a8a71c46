"""How `correct` is decided: served answers against the plain reference.

Every answer is reduced to plain arrays first (`served_fields` for the
program's responses, `answer_fields` for a reference or control answer), so
the comparison sees no type of the program.

Numbers compared, each with its limit:

* `answers_checked`: answers compared, every answered request of the
  window; at least 1 (a run that answers nothing proves nothing);
* `answers_wrong`: those whose anchors or doc-only set differ from the
  reference's answer to the same query; limit 0 (answers are exact);
* `answers_missing`: requests of the window that never got an answer;
  limit 0.
"""
from __future__ import annotations

import numpy as np

from bench.lib.reference import Answer


def served_fields(resp) -> dict:
    """The parts of a served SearchResponse the comparison reads."""
    return {"doc": np.asarray(resp.doc, np.int64),
            "pos": np.asarray(resp.pos, np.int64),
            "doc_only": bool(resp.doc_only)}


def answer_fields(ans: Answer) -> dict:
    """A reference (or control) answer put in the program's place."""
    doc = (ans.codes + (1 << 31)) >> 32
    pos = ans.codes - (doc << 32)
    if not len(doc) and ans.doc_level:
        doc = np.array(sorted(ans.doc_level), np.int64)
        return {"doc": doc, "pos": np.full(len(doc), -1, np.int64),
                "doc_only": True}
    return {"doc": doc, "pos": pos, "doc_only": False}


def compare(got: dict, ans: Answer) -> str | None:
    """What is wrong with one answer, or None."""
    codes = (got["doc"] << 32) + got["pos"]
    if len(ans.codes):
        if got["doc_only"]:
            return "doc-only answer where anchors exist"
        if len(codes) != len(ans.codes) or not np.array_equal(
                np.sort(codes), ans.codes):
            return (f"anchors differ: {len(codes)} served, "
                    f"{len(ans.codes)} expected")
    elif got["doc_only"]:
        if set(got["doc"].tolist()) != ans.doc_level:
            return "doc-only set differs"
    elif len(codes):
        return f"{len(codes)} anchors served, none expected"
    return None


def judge(wrong: int, missing: int, checked: int) -> dict:
    """{name: {value, limit}} of one run."""
    return {"answers_checked": {"value": checked, "min": 1},
            "answers_wrong": {"value": wrong, "limit": 0},
            "answers_missing": {"value": missing, "limit": 0}}


def is_correct(compared: dict) -> bool:
    return all(v["value"] <= v.get("limit", v["value"])
               and v["value"] >= v.get("min", v["value"])
               for v in compared.values())


def describe(name: str, v: dict) -> str:
    """One compared number beside its limit, as a run prints it."""
    bound = (f"limit {v['limit']}" if "limit" in v
             else f"at least {v['min']}")
    return f"compared {name}: {v['value']} ({bound})"
