"""The traffic generator: deterministic per seed, and the same sizes on
every seed, in another order."""

import collections

from chipbench import spec as S, traffic as T


def _mix(name):
    return S.load_json(S.ROOT / "chipbench" / "traffic" / f"{name}.json")


def test_open_loop_is_deterministic_per_seed():
    tr = _mix("chat-short-open")
    a = T.open_loop(tr, 30, 2**31 + 17)
    b = T.open_loop(tr, 30, 2**31 + 17)
    assert a == b and len(a) > 50


def test_open_loop_seeds_send_the_same_work_at_the_same_times():
    tr = _mix("chat-short-open")
    a = T.open_loop(tr, 30, 1)
    b = T.open_loop(tr, 30, 987654321)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert a[-1].arrival_s < 30

    def bursts(rs):
        out = collections.defaultdict(list)
        for r in rs:
            out[r.arrival_s].append((r.prompt_len, r.max_new))
        return out

    ba, bb = bursts(a), bursts(b)
    assert all(sorted(ba[t]) == sorted(bb[t]) for t in ba)
    assert [(r.prompt_len, r.max_new) for r in a] != \
        [(r.prompt_len, r.max_new) for r in b]


def test_sizes_follow_the_file_and_stay_in_bounds():
    tr = _mix("chat-short-open")
    reqs = T.open_loop(tr, 30, 5)
    assert all(tr["prompt"]["min"] <= r.prompt_len <= tr["prompt"]["max"]
               for r in reqs)
    assert all(tr["output"]["min"] <= r.max_new <= tr["output"]["max"]
               for r in reqs)
    assert all(r.prompt_len + r.max_new < tr["max_len"] for r in reqs)
    med = sorted(r.prompt_len for r in reqs)[len(reqs) // 2]
    assert abs(med - tr["prompt"]["median"]) <= 0.15 * tr["prompt"]["median"]


def test_closed_pool_sends_the_same_sizes_on_every_seed():
    tr = _mix("doc-long-closed")
    n = tr["block"]
    a = [next(it) for it in [T.closed_pool(tr)] for _ in range(3 * n)]
    b = [next(it) for it in [T.closed_pool(tr)] for _ in range(3 * n)]
    for k in range(3):
        blk = lambda rs: sorted((r.prompt_len, r.max_new)  # noqa: E731
                                for r in rs[k * n:(k + 1) * n])
        assert blk(a) == blk(b)
    assert [r.prompt_len for r in a] == [r.prompt_len for r in b]
    assert all(r.prompt_len + r.max_new < tr["max_len"] for r in a)


def test_prompt_tokens_depend_on_seed_and_request():
    req = T.Req(3, 0.0, 40, 8)
    a = T.prompt_tokens(7, req, 50304)
    assert (a == T.prompt_tokens(7, req, 50304)).all()
    assert (a != T.prompt_tokens(8, req, 50304)).any()
    assert a.min() >= 0 and a.max() < 50304 and len(a) == 40
