"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed:

* every request of the window was answered (``unanswered``), on the
  route the traffic forced (``routes_wrong``);
* every answered request is a decision-trace record, and the chain of
  all of them verifies (``records_unverified``);
* a sample of the window's requests, drawn from the seed and always
  holding one with the longest prompt and the most members, is read
  token by token: each of its lanes (three probe samples, one lane per
  member its route calls) must be found among the decode launches in
  step order at the right positions, and the probe's text must be what
  the result reports (``lanes_unmatched``); then the plain float32
  reference runs once over each prompt with the lane's served tokens,
  and ``gap_probe`` / ``gap_member`` are the widest gap by which a
  served token lies below the reference's best choice under the lane's
  own sampling noise (``reference/sampling.py``).

With ``control`` the reference also runs in float8 (the control) over
the same prompts and tokens, and the control is judged in the
program's place: ``gap_probe`` / ``gap_member`` are then the gaps of
the control's own first choices, read the same way, and the program's
own gaps are kept beside them in ``win.checked``.
"""
from __future__ import annotations

import tempfile
from pathlib import Path

import numpy as np

from bench import arch, capture, tokenizer, weights
from bench.reference import sampling


def _sample(win, rng) -> list:
    """(call, admission) pairs: one of the requests with the longest
    prompt and the most members, then others drawn from the seed, as
    many as the mix's ``requests`` and until the sample holds its
    ``member_lanes``."""
    cls, want = win.mix["classes"], win.mix["check"]
    reqs = [(ci, a) for ci, c in enumerate(win.calls)
            for a in range(len(c.tasks))]

    def size(r):
        k, mode = win.calls[r[0]].pairs[r[1]]
        return (cls[k]["tokens"], mode)

    def lanes(picked):
        return sum(len(_members(size(r)[1], len(win.models) - 1,
                                win.arena_lite)) for r in picked)
    top = max(map(size, reqs))
    first = [r for r in reqs if size(r) == top]
    pick = [first[rng.integers(len(first))]]
    rest = [reqs[i] for i in rng.permutation(len(reqs))
            if reqs[i] != pick[0]]
    for r in rest:
        if len(pick) >= want["requests"] and \
                lanes(pick) >= want["member_lanes"]:
            break
        pick.append(r)
    return pick


def _members(mode: int, n_members: int, lite: int) -> list:
    return [mi for mi in range(n_members)
            if mode >= (1 if mi < lite else 2)]


def run(win, words, control: bool = False):
    from repro.serving import trace_records
    from repro.teamllm.artifacts import ArtifactStore

    max_new = win.mix["max_new_tokens"]
    n_members = len(win.models) - 1
    routes_wrong = unanswered = 0
    with tempfile.TemporaryDirectory() as d:
        store = ArtifactStore(Path(d) / "runs.jsonl")
        for ci, c in enumerate(win.calls):
            res = c.result
            routes_wrong += int((np.asarray(res.modes) != c.modes).sum())
            answers = list(res.final_answers)
            unanswered += len(c.tasks) - sum(
                isinstance(a, str) for a in answers[:len(c.tasks)])
            for r in trace_records(f"bench-{ci}", c.tasks, res,
                                   [f"member{i + 1}"
                                    for i in range(n_members)]):
                store.append(r)
        audit = store.audit()
    records_unverified = (win.answers - audit["records"]
                          + (0 if audit["ok"] else 1))

    # the served lanes of the sample
    rng = np.random.default_rng(words[6])
    seed = words[5]
    lengths = [k["tokens"] for k in win.mix["classes"]]
    length = max(lengths) + max_new
    unmatched = 0
    todo: dict = {}                      # model -> [(ids, tokens, key, T)]
    by_call: dict = {}
    for ci, a in _sample(win, rng):
        c = win.calls[ci]
        if ci not in by_call:
            by_call[ci] = capture.lanes(c.launches, max_new)
        found = by_call[ci]
        ids = tokenizer.encode(c.tasks[a].text)
        s = len(ids)
        lanes = [(0, sampling.probe_key(seed, a, j), win.temps[0], j)
                 for j in range(3)]
        lanes += [(mi + 1, sampling.member_key(seed, a, mi),
                   win.temps[1], None)
                  for mi in _members(int(c.modes[a]), n_members,
                                     win.arena_lite)]
        for model, key, temp, j in lanes:
            lane = found.get((model, np.asarray(key).tobytes()))
            ok = (lane is not None and not lane["bad"]
                  and lane["positions"] == list(
                      range(s, s + len(lane["tokens"]))))
            if ok and j is not None:
                ok = tokenizer.decode(lane["tokens"]) == \
                    c.result.probe_texts[a][j]
            if not ok:
                unmatched += 1
                continue
            todo.setdefault(model, []).append(
                (ids, lane["tokens"], key, temp))

    gaps = {"probe": [0.0], "member": [0.0]}
    ctrl = {"probe": [0.0], "member": [0.0]}
    served = 0
    for model, items in sorted(todo.items()):
        m = win.models[model]
        logits = arch.of(m).logits
        w = weights.make(m, words[model], win.config["dtype"])
        side = "probe" if model == 0 else "member"
        for ids, toks, key, temp in items:
            seq = np.zeros(length, np.int32)
            body = ids + toks[:-1]
            seq[:len(body)] = body
            ref = logits(m, w, seq)
            other = logits(m, w, seq, "fp8") if control else ref
            g, gc = sampling.gaps(ref, other, len(ids) - 1, toks, key,
                                  temp, max_new)
            gaps[side].append(float(g.max()))
            ctrl[side].append(float(gc.max()))
            served += len(toks)
        del w
    lim = win.config["limits"]
    judged = ctrl if control else gaps
    checks = {
        "gap_probe": {"value": max(judged["probe"]),
                      "limit": lim["gap_probe"]},
        "gap_member": {"value": max(judged["member"]),
                       "limit": lim["gap_member"]},
        "lanes_unmatched": {"value": unmatched, "limit": 0},
        "routes_wrong": {"value": routes_wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "records_unverified": {"value": records_unverified, "limit": 0},
    }
    win.checked = {"tokens": served, "lanes": sum(map(len, todo.values())),
                   "gaps_probe": gaps["probe"][1:],
                   "gaps_member": gaps["member"][1:]}
    if control:
        win.checked.update(program_gap_probe=max(gaps["probe"]),
                           program_gap_member=max(gaps["member"]),
                           control_gaps_probe=ctrl["probe"][1:],
                           control_gaps_member=ctrl["member"][1:])
    return checks, routes_wrong + unanswered
