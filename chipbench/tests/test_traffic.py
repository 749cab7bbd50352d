"""The traffic generator: deterministic per seed, one set of lengths and
budgets for every seed."""
import traffic

MIX = {"requests": 8, "bos_id": 257, "prompt_align": 32,
       "prompt_tokens": {"min": 32, "max": 512, "median": 128,
                         "sigma": 0.8},
       "output_tokens": {"min": 32, "max": 384, "median": 128,
                         "sigma": 0.7}}


def _lens(prompts):
    return [len(p.encode("ascii")) + 1 for p in prompts]     # + BOS


def test_same_seed_same_job():
    assert traffic.job(MIX, 5) == traffic.job(MIX, 5)
    big = 2 ** 31 + 12345
    assert traffic.job(MIX, big) == traffic.job(MIX, big)


def test_seeds_share_lengths_and_budgets_not_tokens():
    p1, b1 = traffic.job(MIX, 1)
    p2, b2 = traffic.job(MIX, 2 ** 31 + 7)
    assert _lens(p1) == _lens(p2) and b1 == b2
    assert p1 != p2


def test_profile_quantiles_clipped_aligned_in_fixed_order():
    lens = traffic.profile(MIX["prompt_tokens"], 8, 32)
    assert all(x % 32 == 0 and 32 <= x <= 512 for x in lens)
    assert len(set(lens)) > 4
    assert lens != sorted(lens)                  # one fixed order, not sorted
    assert lens == traffic.profile(MIX["prompt_tokens"], 8, 32)
    p, _ = traffic.job(MIX, 3)
    assert _lens(p) == lens
    assert all(32 <= ord(c) < 127 for c in "".join(p))


def test_fixed_profile():
    assert traffic.profile({"min": 3584, "max": 3584}, 4, 32) == [3584] * 4
