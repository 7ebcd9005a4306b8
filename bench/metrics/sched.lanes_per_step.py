"""Requests decoding per shared decode step, from the engine's counters
over the whole run."""


def read(run):
    st = run.get("stats") or {}
    return st["decode_lanes"] / st["decode_steps"] \
        if st.get("decode_steps") else None
