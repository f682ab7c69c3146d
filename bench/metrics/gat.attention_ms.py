"""Device ms per window step of GAT's attention: the ops traced under the
program's ``jax.named_scope("gat_attention")`` (scores, masked softmax,
weighted sum), forward and backward together."""
from benchlib import scopes


def read(run):
    return scopes.read(run, "gat_attention")
