"""Latent positions the MLA decode attention reads over the positions live
in the decoding slots (the engine's ``mla_latent_read /
decode_kv_live``), summed over every decode tick of the whole run: the
warm-up and the arrivals before the window count too, so this is the
run's ratio, not the window's alone.  1.0 means decode reads only live
positions; today's decode reads every slot's whole table.  Nothing to
read (no MLA counters): None.  Moves ``itl_p95_ms``."""


def read(run):
    live = run.counters.get("decode_kv_live")
    if not live:
        return None
    return run.counters["mla_latent_read"] / live
