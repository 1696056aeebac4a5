"""Page pools for the paged-attention tests, in the one layout the
serving stack holds: lane-dense pages ``(n_pages + 1, page_size,
hkv * hd)``, page 0 the reserved null page."""
import numpy as np


def random_pools(rng, n_pages: int, page_size: int, hkv: int, hd: int):
    """K and V pools of standard-normal float32 content."""
    shape = (n_pages + 1, page_size, hkv * hd)
    return (rng.normal(size=shape).astype(np.float32),
            rng.normal(size=shape).astype(np.float32))

