"""The rows whose recurrent state a decode step advanced, in the
traced seconds: the growth of kfx_lm_ssm_row_updates_total (rows x
state-space layers, counted on the device by the layers; pads and
inactive rows are not) between the two scrapes the traced replica
makes of itself at the trace's edges
(benchmark/workers/traced_replica_scraped.py), over the decode steps
the engine made between them (the growth of kfx_lm_engine_chunks_total
x the tokens a chunk) and the state-space layers. The steps are the
ones whose device time the trace holds; the engine raises the two
counters together, once a chunk's outputs are on the host. Finds
nothing to read (None) where the trace carries no such scrapes or the counter did not
grow: a program or a configuration without it."""


def live_rows(ctx):
    counters = (ctx.get("trace") or {}).get("counters")
    cfg = ctx.get("cfg", {})
    if not counters or "layer_types" not in cfg:
        return None
    grew = lambda n: (counters["after"].get(n, 0.0)
                      - counters["before"].get(n, 0.0))
    steps = (grew("kfx_lm_engine_chunks_total")
             * ctx["serving"]["decode_chunk"])
    updates = grew("kfx_lm_ssm_row_updates_total")
    if steps <= 0 or updates <= 0:
        return None
    return updates / (steps * sum(k == "mamba" for k in cfg["layer_types"]))


def read(ctx, args):
    return live_rows(ctx)
