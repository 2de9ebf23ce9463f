"""One runner per traffic `kind`: `run(cell, args, out_dir, t_start) -> dict`
with keys `correct`, `attempted`, `failed`, `end_to_end`, `facts`, `device`
and, from a traced run, `breakdown`."""
