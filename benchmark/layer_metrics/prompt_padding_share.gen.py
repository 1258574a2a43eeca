"""Prompt slots that held padding: the program's gen.padded_prompt_tokens over the answered requests' slots, gen.requests x P_max (both counted where an answer is fetched; the engine's batch_slots is counted at dispatch, up to `depth` batches ahead, and read 23% where the prompts hold 25%; rows no request filled are engine_batch_fill's). The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.requests") or "gen.padded_prompt_tokens" not in c:
        return None
    return (100.0 * c["gen.padded_prompt_tokens"]
            / (c["gen.requests"] * rec.traffic["p_max"]))
