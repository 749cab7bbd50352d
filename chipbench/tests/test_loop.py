"""The recording ServeLoop keeps every request's served tokens."""
import run
import tiny
import traffic
from loop import play


def test_records_every_request_as_the_parent_cuts_it():
    _, c = tiny.cell("starcoder2.chat_bucket")
    c["traffic"].update(requests=6, batch_size=4)     # two waves, swaps
    loop = run.build(c["config"], c["traffic"], 3)
    prompts, budgets = traffic.job(c["traffic"], 3)
    texts = loop.serve(prompts, budgets)
    job = play(loop, prompts, budgets)
    eos = loop.engine.tok.eos_id
    assert len(job["served"]) == len(prompts)
    for text, served, budget in zip(texts, job["served"], budgets):
        assert served is not None and 1 <= len(served) <= budget
        assert eos not in served
        assert loop.engine.tok.decode(served) == text
    counts = job["counts"]
    assert counts["prefills"] == len(
        [s for s in job["shapes"] if s[0] == "prefill"]) >= job["stats"]["waves"]
    assert counts["decode_steps"] > 0
    assert counts["decode_row_steps"] == 4 * counts["decode_steps"]
    # served tokens are the engine's greedy choices: replaying is exact
    again = play(loop, prompts, budgets)
    assert again["served"] == job["served"]
