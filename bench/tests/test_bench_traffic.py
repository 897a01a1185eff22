"""The chat and unique schedules: a pure function of the seed, prefix
extension from turn to turn, Zipf-heavy template reuse, the max_len
cut, and the same sizes for every seed."""
import json
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import loadgen  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
UNIQUE = json.loads((BENCH / "traffic" / "unique.json").read_text())
V, MAX_LEN = 151552, 4096


def chat(seed, rate=0.5, horizon=200.0):
    return loadgen.chat_schedule(CHAT, rate, seed, horizon, V, MAX_LEN)


def sessions(s):
    out = {}
    for t in s.turns:
        out.setdefault(t.session, []).append(t)
    return out


def test_chat_is_a_function_of_the_seed():
    a, b, c = chat(7), chat(7), chat(2**31 + 7)
    assert [(t.t, t.prompt, t.max_new) for t in a.turns] == \
        [(t.t, t.prompt, t.max_new) for t in b.turns]
    assert [t.prompt for t in a.turns] != [t.prompt for t in c.turns]


def test_turns_extend_their_own_prefix():
    for turns in sessions(chat(3)).values():
        for prev, nxt in zip(turns, turns[1:]):
            assert nxt.prompt[:len(prev.prompt)] == prev.prompt
            # the previous reply and a new input of >= 64 tokens follow
            assert len(nxt.prompt) >= len(prev.prompt) + prev.max_new + 64
            assert nxt.t >= prev.t + 10.0
        assert turns[-1].last and not any(t.last for t in turns[:-1])


def test_templates_are_shared_and_zipf_heavy():
    s = chat(5, rate=2.0)
    firsts = [ts[0] for ts in sessions(s).values()]
    counts = Counter(t.template for t in firsts)
    assert counts.most_common(1)[0][0] == 0          # rank 1 dominates
    assert counts[0] > len(firsts) / 3
    by_tpl = {}
    for t in firsts:
        head = tuple(t.prompt[:CHAT["sessions"]["template_tokens"]])
        by_tpl.setdefault(t.template, set()).add(head)
    assert all(len(heads) == 1 for heads in by_tpl.values())


def test_max_len_cut():
    for t in chat(9, rate=2.0).turns:
        assert len(t.prompt) + t.max_new + 1 <= MAX_LEN
    # with a short context every session is cut short of 5 turns
    short = loadgen.chat_schedule(CHAT, 2.0, 9, 200.0, V, 2000)
    assert all(len(t.prompt) + t.max_new + 1 <= 2000 for t in short.turns)
    assert max(len(ts) for ts in sessions(short).values()) < 5


def test_every_seed_offers_the_same_work_at_the_same_times():
    a, b = chat(1), chat(2)
    assert [(t.t, len(t.prompt), t.max_new, t.session) for t in a.turns] \
        == [(t.t, len(t.prompt), t.max_new, t.session) for t in b.turns]
    assert [t.prompt for t in a.turns] != [t.prompt for t in b.turns]


def test_unique_prompts_share_nothing_and_fit():
    mix = dict(UNIQUE, per_client=3)
    s = loadgen.unique_schedule(mix, 4, 11, V, MAX_LEN)
    reqs = [t for q in s.clients for t in q]
    assert len(reqs) == 12
    heads = {tuple(t.prompt[:16]) for t in reqs}
    assert len(heads) == len(reqs)
    for t in reqs:
        assert 128 <= len(t.prompt) <= 3072
        assert len(t.prompt) + t.max_new + 1 <= MAX_LEN
    s2 = loadgen.unique_schedule(mix, 4, 12, V, MAX_LEN)
    assert [[len(t.prompt) for t in q] for q in s.clients] == \
        [[len(t.prompt) for t in q] for q in s2.clients]
