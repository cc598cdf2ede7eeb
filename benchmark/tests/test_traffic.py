"""The generators: the same seed gives the same schedule and lengths,
another seed another arrangement of the SAME multiset of sizes."""

import collections

import pytest

import traffic

MIXES = ["sat-chat", "long-prompt"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan_other_seed_other_order_same_sizes(name):
    mix = traffic.load_mix(name)
    kind = traffic.load_kind(mix["kind"])
    pool = traffic.size_pool(mix)
    a, b = kind.plan(mix, pool, 8, 3000000001), kind.plan(mix, pool, 8, 3000000001)
    c = kind.plan(mix, pool, 8, 7)
    assert a == b
    assert a["requests"] != c["requests"]
    flat = lambda p: collections.Counter(s for reqs in p["requests"] for s in reqs)  # noqa: E731
    assert flat(a) == flat(c)
    assert sorted(a["starts"]) == sorted(c["starts"])


@pytest.mark.parametrize("name", MIXES)
def test_pool_keeps_to_the_files_limits_and_needs_no_seed(name):
    mix = traffic.load_mix(name)
    pool = traffic.size_pool(mix)
    assert pool == traffic.size_pool(mix) and len(pool) == mix["pool"]
    for n_prompt, n_out in pool:
        assert mix["prompt_len"].get("min", 0) <= n_prompt <= mix["prompt_len"].get("max", 10**9)
        assert n_out >= 2


def test_sat_chat_shape():
    mix = traffic.load_mix("sat-chat")
    prompts = sorted(p for p, _o in traffic.size_pool(mix))
    outs = sorted(o for _p, o in traffic.size_pool(mix))
    assert 200 <= prompts[len(prompts) // 2] <= 300          # median near 256
    assert prompts[0] >= 32 and prompts[-1] == 1024 and 32 <= outs[0] and outs[-1] <= 96
    assert traffic.load_kind("closed").plan(mix, traffic.size_pool(mix), 5, 1)["clients"] == 5


def test_long_prompt_shape():
    mix = traffic.load_mix("long-prompt")
    pool = traffic.size_pool(mix)
    assert all(2048 <= p <= 3584 and o == 4 for p, o in pool)
    assert len({p for p, _o in pool}) == len(pool)  # sixteen distinct lengths


def test_prompt_ids_differ_by_request_and_repeat_by_seed():
    a = traffic.prompt_ids(5, 0, 0, 64, 1000)
    assert a == traffic.prompt_ids(5, 0, 0, 64, 1000)
    assert a != traffic.prompt_ids(5, 0, 1, 64, 1000) != traffic.prompt_ids(6, 0, 0, 64, 1000)
    assert a[:8] != traffic.prompt_ids(5, 1, 0, 64, 1000)[:8]  # no shared prefix
    assert all(0 <= t < 1000 for t in a)


def test_bucket_len_is_the_programs():
    from inferd_tpu.core.generate import bucket_len

    for n in (1, 16, 17, 64, 65, 1000, 1024, 3584, 4096):
        assert traffic.bucket_len(n) == bucket_len(n)


def test_unknown_kind_and_distribution_are_errors():
    with pytest.raises(FileNotFoundError):
        traffic.load_kind("nothing")
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.5)
