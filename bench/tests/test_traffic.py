import numpy as np

from harness import spec, traffic


def _mix(name):
    return spec.load_json(spec.BENCH / "traffic" / f"{name}.json")


def test_same_seed_same_traffic():
    m = _mix("chat_open")
    a = traffic.generate(m, seed=2 ** 31 + 5, seconds=30, vocab=1000,
                         capacity=2048)
    b = traffic.generate(m, seed=2 ** 31 + 5, seconds=30, vocab=1000,
                         capacity=2048)
    assert [x.due for x in a] == [x.due for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_the_sizes():
    m = _mix("longctx_closed")
    a = traffic.generate(m, seed=1, seconds=30, vocab=32000, capacity=32768)
    b = traffic.generate(m, seed=2 ** 33, seconds=30, vocab=32000,
                         capacity=32768)
    assert sorted(len(x.prompt) for x in a) == \
        sorted(len(x.prompt) for x in b)
    assert all(len(x.prompt) + x.max_new == 32768 for x in a)


def test_chat_lengths_fit_the_context():
    m = _mix("chat_open")
    arr = traffic.generate(m, seed=3, seconds=30, vocab=100, capacity=2048)
    assert all(64 <= len(x.prompt) <= 1536 for x in arr)
    assert all(16 <= x.max_new and len(x.prompt) + x.max_new <= 2048
               for x in arr)
    due = np.array([x.due for x in arr])
    assert np.all(np.diff(due) >= 0) and due[0] < 0 < due[-1] < 30


def test_open_schedule_is_the_same_for_every_seed():
    m = _mix("chat_open")
    runs = [traffic.generate(m, seed=s, seconds=51, vocab=100,
                             capacity=2048) for s in (1, 2 ** 31 + 9)]
    shape = [[(a.due, len(a.prompt), a.max_new) for a in arr]
             for arr in runs]
    assert shape[0] == shape[1]
    assert sum(1 for d, _, _ in shape[0] if 0 <= d < 51) == \
        round(m["rate_per_s"] * 51)
    assert not np.array_equal(runs[0][0].prompt, runs[1][0].prompt)
