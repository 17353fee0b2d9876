"""The one traffic generator: reads a mix file and yields the statements to
send. A mix is data (`traffic/<mix>.json`):

  streams   closed-loop client streams, each sending its next statement when
            the last page of the previous one is decoded; 1 is the only
            number there is so far
  loop      "closed" (an open loop needs in-flight statements; PERF.md)
  queries   [{"id": <query id>, "weight": w}, ...]: each statement's query is
            drawn by weight from the seed
  params    "fixed": the query file's `params.fixed`, one text repeated;
            "sets": the query file's `params.sets`, all of them in an order
            drawn from the seed, then all again in another order: every
            seed sends every text equally often
  warmup    statements of each distinct text sent before the window, at the
            least; `warmup_seconds`: and each text for at least this long
  traced_seconds / traced_min_statements
            how much of a `--trace 1` window the profiler covers
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterator, List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("loop", "closed") != "closed":
        raise ValueError(f"traffic {name}: loop {mix['loop']!r} is not "
                         "implemented (closed only)")
    if int(mix.get("streams", 1)) != 1:
        raise ValueError(f"traffic {name}: streams {mix['streams']!r} is not "
                         "implemented (one stream only)")
    if mix.get("params", "fixed") not in ("fixed", "sets"):
        raise ValueError(f"traffic {name}: params {mix['params']!r}")
    return mix


def load_query(qid: str) -> dict:
    base = os.path.join(HERE, "queries", qid)
    with open(base + ".json") as f:
        meta = json.load(f)
    with open(base + ".sql") as f:
        meta["template"] = f.read()
    return meta


def param_sets(mix: dict, meta: dict) -> List[dict]:
    if mix.get("params", "fixed") == "fixed":
        return [meta["params"]["fixed"]]
    return meta["params"]["sets"]


def params_key(params: dict) -> str:
    return json.dumps(params, sort_keys=True)


def stream(mix: dict, queries: Dict[str, dict],
           seed: int) -> Iterator[Tuple[str, dict, str]]:
    """Endless (query id, params, sql text)."""
    rng = np.random.default_rng(int(seed))
    ids = [q["id"] for q in mix["queries"]]
    w = np.array([q.get("weight", 1.0) for q in mix["queries"]], float)
    w /= w.sum()
    pending: Dict[str, List[int]] = {qid: [] for qid in ids}
    while True:
        qid = ids[int(rng.choice(len(ids), p=w))] if len(ids) > 1 else ids[0]
        sets = param_sets(mix, queries[qid])
        if not pending[qid]:  # a new pass over this query's texts
            pending[qid] = [int(i) for i in rng.permutation(len(sets))]
        params = sets[pending[qid].pop()]
        yield qid, params, queries[qid]["template"].format(**params).strip()


def warmup_texts(mix: dict, queries: Dict[str, dict]) -> List[Tuple[str, str]]:
    """Every distinct text the window can send: (query id, sql)."""
    seen, out = set(), []
    for q in mix["queries"]:
        for params in param_sets(mix, queries[q["id"]]):
            sql = queries[q["id"]]["template"].format(**params).strip()
            if sql not in seen:
                seen.add(sql)
                out.append((q["id"], sql))
    return out
