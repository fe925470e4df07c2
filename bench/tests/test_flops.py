"""Operation counts against the hand counts of the paper CNN."""
import json

from bench.flops import fl_cnn
from bench.tests.conftest import ROOT


def _cfg():
    config = json.loads((ROOT / 'bench/configs/paper-cnn.json').read_text())
    traffic = json.loads((ROOT / 'bench/traffic/k20.packed.json').read_text())
    return config, traffic


def test_cnn_macs_per_image_match_the_hand_count():
    # conv1 4704 x 75 + conv2 1600 x 150 + fc 48000 + 10080 + 840
    assert fl_cnn.macs_per_image() == 651_720


def test_cnn_round_flops_match_the_hand_count():
    config, traffic = _cfg()
    # 6 FLOPs per multiply-add (x2 FLOPs, x3 forward + backward) over
    # 20 clients x 500 images: 39.1 GFLOP a round
    assert fl_cnn.model_flops_per_round(config, traffic) == (
        6 * 651_720 * 10_000)


def test_wire_words_of_the_paper_payload():
    # n = 62,006 coordinates in 1,938 groups of 32: sign 4 + 1938 + 1,
    # modulus 7 + 3 * 1938 + 1 words
    assert fl_cnn.wire_words(62_006, 3) == (1943, 5822)


def test_kernel_work_counts_unpadded_operands():
    config, traffic = _cfg()
    nbytes, flops = fl_cnn.kernel_work_per_round(config, traffic)
    words = 1943 + 5822
    payload = 1938 + 3 * 1938
    assert nbytes == 4 * (3 * 20 * words + 20 * payload + 3 * 62_006)
    assert flops == 4 * 20 * 62_006
