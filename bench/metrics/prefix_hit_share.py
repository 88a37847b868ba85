"""Backend/cache: prompt tokens served from resident shared pages, as a
share of the prompt tokens admitted in the window (the program's
registry counters `backend/shared_tokens` / `backend/prompt_tokens`)."""
UNIT = "%"


def read(rec):
    prompt = rec.counters.get("backend/prompt_tokens", 0)
    if prompt <= 0:
        return None
    return 100.0 * rec.counters.get("backend/shared_tokens", 0) / prompt
