"""Device ms per window step of GAT's projections: the ops traced under the
program's ``jax.named_scope("gat_project")`` (the shared projection of
every row and the skip linear), forward and backward together."""
from benchlib import scopes


def read(run):
    return scopes.read(run, "gat_project")
