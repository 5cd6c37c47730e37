"""The Pallas kernels the models call: flash attention
(``flash_attention.py``), the chunked SSD scan (``ssd_chunk.py``) and the
xBC causal conv with its SiLU (``causal_conv.py``), each with its own
backward.  ``ops`` decides where each one runs, and how: the models ask it
why a kernel cannot run here, and call its entries or their own XLA paths.
``ref`` holds the flash kernel's oracle."""
