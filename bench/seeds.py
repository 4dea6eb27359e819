"""Seeds: ``--seed`` is any whole number up to a little over 2**31."""
import jax


def base_key(seed: int):
    """A PRNG key from a seed of any size: the low 31 bits make the key,
    the bits above are folded in."""
    k = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
