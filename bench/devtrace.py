"""From a profiler trace to the numbers the per-layer readers take.

``load(path, scopes)`` reads the ``.xplane.pb`` that ``jax.profiler``
writes, with nothing but JAX, into plain event records (JSON-able, so a
small recorded trace lives in ``bench/data`` for the tests):

  {"chip": 0, "name": "fusion.12", "scope": "jit(chunk_replay)/...",
   "start": ns, "dur": ns}            one operation on a chip
  {"chip": 0, "name": "all-gather-start.1", "async": True, ...}
                                      a collective in flight ("Async XLA
                                      Ops" line: from its start to its done)
  {"chip": -1, "name": "bench/window", "start": ns, "dur": ns}
                                      one event of the host's Python thread

A TPU trace names each operation by its HLO instruction and carries no
JAX name stack, so ``scopes`` maps instruction names to the ``op_name`` of
their metadata in the compiled program's HLO text (``hlo_scopes``). The
"XLA Ops" line nests: a loop or conditional spans the operations of its
body and the gaps between them, in which the chip waits. So only the
innermost operations count, the leaves: those with no operation inside.

``summarize(events, steps)`` reduces them over the window, the stretch from
the first operation to the end of the last on any chip:

- ``busy_s``: the union of a chip's leaf intervals, averaged over chips;
  ``idle_share`` is 1 - busy / window;
- ``train_s`` / ``exchange_s``: time of leaves whose scope lies under the
  autodiff of the mule loss (``jvp(`` or ``transpose(``), and of every
  other leaf, averaged over chips; together they are ``busy_s``;
- ``collective_exposed_s``: time in which a chip runs or awaits a
  collective (all-gather, all-reduce, collective-permute, reduce-scatter,
  all-to-all; a leaf, or in flight on the "Async XLA Ops" line) and no
  other leaf, averaged over chips. A collective in flight counts only
  here, never as busy;
- ``top_ops`` and ``idle_gaps`` for the run's ``breakdown``: the leaves
  with most time (by instruction, over all chips), and the longest gaps
  between leaves on the first chip, each named by the innermost host
  event it starts in.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")
TRAIN_MARKS = ("jvp(", "transpose(")
_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(m.group(2))
            out[m.group(1)] = op.group(1) if op else ""
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _chip(plane_name: str) -> Optional[int]:
    """'/device:TPU:3' -> 3; planes of anything else -> None."""
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    rest = plane_name[len(head):]
    return int(rest) if rest.isdigit() else None


def instruction(event_name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str, scopes: Dict[str, str]) -> List[Dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        chip = _chip(plane.name)
        for line in plane.lines:
            if chip is not None and line.name == "XLA Ops":
                for e in line.events:
                    name = instruction(e.name)
                    out.append({"chip": chip, "name": name,
                                "scope": scopes.get(name, ""),
                                "start": e.start_ns, "dur": e.duration_ns})
            elif chip is not None and line.name == "Async XLA Ops":
                for e in line.events:
                    name = instruction(e.name)
                    if is_collective(name):
                        out.append({"chip": chip, "name": name, "scope": "",
                                    "start": e.start_ns,
                                    "dur": e.duration_ns, "async": True})
            elif chip is None and plane.name.startswith("/host:CPU") \
                    and line.name.startswith("python"):
                for e in line.events:
                    out.append({"chip": -1, "name": e.name,
                                "start": e.start_ns, "dur": e.duration_ns})
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _span(events: List[Dict]) -> List[Tuple[float, float]]:
    return _union([(e["start"], e["start"] + e["dur"]) for e in events])


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a, b) -> float:
    """Length of the union ``a`` not covered by the union ``b``."""
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        out += max(0.0, e - cur)
    return out


def leaves(ops: List[Dict]) -> List[Dict]:
    """One chip's operations that hold no other operation (a nested event
    lies wholly inside its parent; of two with the same span, the later
    listed is the inner one)."""
    end = lambda i: ops[i]["start"] + ops[i]["dur"]
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start"],
                                                   -ops[i]["dur"]))
    parent = [False] * len(ops)
    stack: List[int] = []
    for i in order:
        while stack and end(stack[-1]) <= ops[i]["start"]:
            stack.pop()
        if stack and end(i) <= end(stack[-1]):
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(ops, parent) if not p]


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def is_train(scope: str) -> bool:
    return any(m in scope for m in TRAIN_MARKS)


def summarize(events: List[Dict], steps: int) -> Dict:
    ops = [e for e in events if e["chip"] >= 0 and not e.get("async")]
    in_flight = [e for e in events if e["chip"] >= 0 and e.get("async")]
    if not ops or steps <= 0:
        return {}
    host = [e for e in events if e["chip"] < 0]
    chips = sorted({e["chip"] for e in ops})
    t0 = min(e["start"] for e in ops)
    t1 = max(e["start"] + e["dur"] for e in ops)
    busy = train = exchange = exposed = 0.0
    by_name: Dict[str, float] = {}
    leaf_ops = []
    for c in chips:
        mine = leaves([e for e in ops if e["chip"] == c])
        leaf_ops += mine
        busy += _length(_span(mine))
        for e in mine:
            if is_train(e["scope"]):
                train += e["dur"]
            else:
                exchange += e["dur"]
            by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"] * 1e-9
        coll = _span([e for e in mine if is_collective(e["name"])]
                     + [a for a in in_flight if a["chip"] == c])
        other = _span([e for e in mine if not is_collective(e["name"])])
        exposed += _minus(coll, other)
    n = len(chips)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {"chips": n, "steps": steps, "window_s": (t1 - t0) * 1e-9,
            "busy_s": busy * 1e-9 / n, "train_s": train * 1e-9 / n,
            "exchange_s": exchange * 1e-9 / n,
            "collective_exposed_s": exposed * 1e-9 / n,
            "has_collectives": any(is_collective(e["name"])
                                   for e in ops + in_flight),
            "top_ops": [[k, v] for k, v in top],
            "idle_gaps": _gaps(leaf_ops, host, chips[0])}


def _gaps(ops, host, chip, n: int = 10):
    iv = _span([e for e in ops if e["chip"] == chip])
    gaps = sorted(((iv[i + 1][0] - iv[i][1], iv[i][1])
                   for i in range(len(iv) - 1)), reverse=True)[:n]
    out = []
    for length, at in gaps:
        inside = [h for h in host if h["start"] <= at < h["start"] + h["dur"]]
        name = min(inside, key=lambda h: h["dur"])["name"] if inside \
            else "no host event"
        out.append([name, length * 1e-9])
    return out
