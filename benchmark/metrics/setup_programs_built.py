"""Programs the program's own set-up built or fetched: the ``compile`` events
sited inside a ``create_parameter`` span or inside the first
``compile_train_step`` step, from the program's ring. The benchmark's own
programs (seeding the weights, the checks' norms, the reference) are sited
in neither and left out."""
from ..lib import program_setup


def read(record):
    got = program_setup.records()
    return None if got is None else got.built
