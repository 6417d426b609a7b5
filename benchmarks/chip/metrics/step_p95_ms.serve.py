"""95th percentile of the serving engine's whole tick: the program's
``serve/step`` spans (admissions with their prefills, then each lane's
decode and its per-row accounting) that ended in the measured window, in
ms."""
import harness


def read(ctx):
    t0, t1 = ctx["t_open"], ctx["t_close"]
    d = [e - s for ph, c, n, s, e, *_ in ctx.get("spans", [])
         if ph == "X" and c == "serve" and n == "step" and t0 < e <= t1]
    return 1e3 * harness.percentile(d, 95) if d else None
