"""device: % of the traced device-busy time that no ``mxtpu.*`` scope
names (ops that carry none, programs with no live executable), after
the instructions the compiler inserted took their consumer's scope (the
table's ``inherited_ms``): the instrument's own coverage, lower is
better."""
from chipbench.harness import device_scopes


def read(obs):
    return device_scopes.unscoped_share(obs)
