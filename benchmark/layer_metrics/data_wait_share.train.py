"""Share of the window the trainer's loop spent waiting for its next batch:
the sum of its own `data_wait` spans (spans.jsonl) that started in the window
over the window."""

LAYER = "trainer host loop"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def read(obs: dict):
    if obs.get("kind") != "train":
        return None
    t0, t1 = obs["window"]
    waited = sum(s["dur"] for s in obs["spans"] if s["name"] == "data_wait")
    return 100.0 * waited / (t1 - t0)
