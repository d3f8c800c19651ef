"""A number the harness itself observed over the window, times `scale`:
`compiles_in_window` (programs lowered inside it, cache hit or not) or
`memory_peak_bytes` (peak_bytes_in_use of the fullest chip)."""


def read(run, obs, field, scale=1.0):
    value = getattr(run, field)
    return None if value is None else value * scale
