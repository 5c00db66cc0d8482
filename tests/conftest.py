import numpy as np
import pytest
from hypothesis import settings

# Property tests jit-compile on their first example, which overruns
# hypothesis' 200 ms default deadline; the example sequence is
# derandomized so a tier-1 run is reproducible.
settings.register_profile("repro", deadline=None, derandomize=True)
settings.load_profile("repro")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
