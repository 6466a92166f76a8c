"""Model hub: real checkpoints + real tokenizers for the serving stack.

Everything in the serving stack below this package (paged KV, prefix
reuse, int8 blocks, fused attention, speculative decoding) is certified
on synthetic vocab/weights. This package closes that gap with three
pieces that together make the engine a believable product:

  safetensors_io  minimal safetensors reader/writer: 8-byte header-length
                  prefix + JSON header + raw tensor bytes. Reads are LAZY
                  per-tensor mmap slices (no torch, no full-file load);
                  the writer exists for fixtures and round-trip tests.
  tokenizer       GPT-2-family byte-level BPE: vocab.json/merges.txt
                  loader, the byte<->unicode tables, special-token
                  handling, and an IncrementalDetokenizer that holds back
                  incomplete UTF-8 sequences so token-at-a-time streaming
                  never emits mojibake.
  checkpoint      gpt2-class safetensors -> the transformer's param tree:
                  name-mapping table, Conv1D->dense layout detection,
                  fused-qkv splitting, tied embeddings, and per-leaf
                  sharded device_put by the existing partition rules so a
                  host never materializes the full model twice.

`load_model(path)` ties them together into a ModelBundle (config, params,
tokenizer, eos id, model id) ready to drop into PagedDecodeEngine;
`ray_tpu.serve.openai_api` serves such a bundle behind
an OpenAI-compatible `/v1/completions` endpoint.
"""

from .safetensors_io import (  # noqa: F401
    SafetensorsFile,
    load_file,
    save_file,
)
from .tokenizer import (  # noqa: F401
    ByteBPETokenizer,
    IncrementalDetokenizer,
    bytes_to_unicode,
)
from .checkpoint import (  # noqa: F401
    GPT2_NAME_MAP,
    ModelBundle,
    config_from_json,
    load_gpt2_params,
    load_model,
)
from .measure import measure_realtext_spec  # noqa: F401
