# One Pallas kernel: delayed_decode.py (paper Algorithm 5, the batched
# decode every engine read and scan runs on the chip).  ops.py packs its
# tables; ref.py holds its pure-jnp oracle.
