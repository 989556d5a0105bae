"""ops / kernels: of the positions the latent attention of a DECODE round
ran over, the share that a request had written, in %: the program's own
counts ``mxtpu_mla_live_positions_total`` (each decoding row's offset + 1,
summed over the attention layers' calls) over
``mxtpu_mla_page_positions_total`` (rows x the page's length: the absorbed
attention reads every row of a dense page and masks what lies past a
slot's offset), summed over the decode dispatches read inside the window
(``pangu_moe_server.decode_calls``: prefills, the lone-row probe, the ramp
and the drain are outside).  100 when decode reads only what is written.
A program without the counts gives None."""
from chipbench.harness import resolve


def read(obs):
    got = resolve.load_module("models", "pangu_moe_server").decode_calls(obs)
    if got is None or not got["mxtpu_mla_page_positions_total"]:
        return None
    return 100.0 * got["mxtpu_mla_live_positions_total"] \
        / got["mxtpu_mla_page_positions_total"]
