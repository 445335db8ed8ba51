"""The benchmark's operation counts against hand counts at small shapes."""
import bench_testlib  # noqa: F401  (puts the repo on sys.path)
from bench import flops

CFG = {"num_clients": 2, "num_classes": 10, "image_shape": [8, 8, 3],
       "conv_channels": [4, 8], "d_bottleneck": 8, "dense_units": [16]}


def test_paper_forward_by_hand():
    # conv1 8x8 out, 3->4: 2*64*9*3*4 = 13,824; pool to 4x4;
    # conv2 4x4 out, 4->8: 2*16*9*4*8 = 9,216; pool to 2x2, feat 2*2*8 = 32
    assert flops.paper_conv_flops(CFG) == [13824, 9216]
    assert flops.paper_feat_dim(CFG) == 32
    f = flops.paper_forward_flops(CFG)
    head = 2 * 2 * 32 * 8                        # mu and logvar: 1,024
    assert f["encoders"] == 2 * (13824 + 9216 + head)
    assert f["decoder"] == 2 * (16 * 16) + 2 * (16 * 10)   # 16->16->10
    assert f["branch_heads"] == 2 * 2 * 8 * 10


def test_paper_train_leaves_out_the_data_gradient():
    fwd = flops.paper_predict_flops(CFG)
    assert flops.paper_train_flops(CFG) == 3 * fwd - 2 * 13824


def test_paper_model_at_the_cells_widths():
    cfg = dict(CFG, num_clients=5, image_shape=[32, 32, 3],
               conv_channels=[32, 64, 128], d_bottleneck=64,
               dense_units=[512, 256])
    f = flops.paper_forward_flops(cfg)
    # three 3x3 convs of 21.2 MFLOP per view with the head, and 0.6 MFLOP
    # of fusion MLP
    assert round(f["encoders"] / 5 / 1e6, 1) == 21.2
    assert round(f["decoder"] / 1e6, 1) == 0.6
