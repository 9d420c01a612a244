"""Host bytes of the gang inputs (`gang_nodes`, `gang_ok`, `group_onehot`)
the solves handed to the residency over the window (the model's own counter,
`resident_stats()["gang_input_bytes_total"]`, a server's
`hq_solve_gang_input_bytes_total`) per tick."""


def read(observed):
    before, after = observed.get("uploads_before"), observed.get("uploads_after")
    if not before or not after or not observed.get("ticks"):
        return None
    if "gang_input_bytes_total" not in after:
        return None  # a host solve, or a program without this counter
    return (after["gang_input_bytes_total"]
            - before.get("gang_input_bytes_total", 0)) / observed["ticks"]
