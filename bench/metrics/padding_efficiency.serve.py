"""Real atoms over the padded atom-slots the bucketed pools' steps
computed on (`ServeMetrics` atoms_real / atoms_padded); 1 is no waste."""


def read(ctx):
    m = ctx.serve_metrics
    if m is None or not m.atoms_padded:
        return None
    return m.atoms_real / m.atoms_padded
